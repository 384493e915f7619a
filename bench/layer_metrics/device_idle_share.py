"""1 - (union of device-op intervals / traced window), averaged over the
cell's devices."""


def read(ctx):
    return ctx["trace"].idle_share()
