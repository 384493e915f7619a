"""The env step's share of its roofline, in %: the least time in which the
chip could move the bytes the step interface must move (carry in and out,
actions, the returned transitions; `work.step_interface_bytes`) at peak HBM
bandwidth, over the device time of the ops under `bench.env_step`. The
step does next to no arithmetic, so the memory term is the bound."""
from peaks import peaks
from work import roofline_seconds


def read(ctx):
    tr = ctx["trace"]
    busy = tr.seconds(tr.ops(scope="bench.env_step"))
    if busy <= 0:
        return None
    n_bytes = (ctx["interface_bytes"] * ctx["stats"]["chunks"]
               / tr.n_devices)
    return 100.0 * roofline_seconds(n_bytes, 0.0,
                                    peaks(ctx["device_kind"])) / busy
