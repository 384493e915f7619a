"""The rasteriser's share of its roofline, in %: one H x W frame per env
step at the observation dtype (`work.frame_bytes`) written at peak HBM
bandwidth, over the device time of the rasteriser kernel."""
from peaks import peaks
from work import roofline_seconds


def read(ctx):
    if "frame_bytes" not in ctx:
        return None
    tr = ctx["trace"]
    busy = tr.seconds(tr.ops(kernel="_raster_kernel"))
    if busy <= 0:
        return None
    n_bytes = ctx["frame_bytes"] * ctx["stats"]["chunks"] / tr.n_devices
    return 100.0 * roofline_seconds(n_bytes, 0.0,
                                    peaks(ctx["device_kind"])) / busy
