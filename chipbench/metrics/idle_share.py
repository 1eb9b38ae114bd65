"""Device idle share: 1 - (union of the device's operation intervals) /
the traced window, in %, averaged over the chips used."""
from chipbench import trace


def read(ctx):
    devices = ctx.trace.devices()[:ctx.chips]
    if not devices:
        return None
    busy = sum(trace.busy_s(ctx.trace, d) for d in devices) / len(devices)
    return 100.0 * (1.0 - busy / ctx.trace.window_s)
