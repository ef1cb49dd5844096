"""Dense slot tick on the host: per ``scheduler.tick`` span, its length
minus the device-busy time inside it (ms), averaged over the window's
ticks.  Covers the plane restack, upload and download of
``DenseStepper.step`` and the harvest of ``SlotScheduler._harvest``."""
from tpubench import xplane


def read(ctx):
    ticks = ctx.host_spans("scheduler.tick")
    if not ticks or not ctx.trace.devices:
        return None
    host = [(e - s) / 1e9 - xplane.busy_in(ctx.trace, s, e)
            for s, e, _ in ticks]
    return sum(host) / len(host) * 1e3
