"""Layer: device. 1 - (union of device op intervals) over the traced
window, in percent, mean over the devices used."""

from harness import trace as T


def read(ctx):
    return None if ctx.trace is None else T.idle_share(ctx.trace)
