"""Share of the traced window, in %, in which the chip waits between the
end of one execution of the scanned round segment and the start of the
next: the engine's host work between segments (dispatch, absorbing the
round log, syncing the device registry back)."""
from chipbench import trace

SEGMENT = "_segment"     # the XLA module of ScanRunner's jitted segment


def read(ctx):
    devices = ctx.trace.devices()[:ctx.chips]
    if not devices:
        return None
    gaps = [trace.between_executions(ctx.trace, d, SEGMENT) for d in devices]
    if not any(e for d in devices for e in ctx.trace.modules.get(d, [])
               if SEGMENT in e.name):
        return None
    return 100.0 * (sum(gaps) / len(gaps)) / ctx.trace.window_s
