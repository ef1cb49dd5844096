"""Device: share of the traced window in which no operation ran on the
chip (1 - busy union / window), from the profiler trace."""
from tpubench import xplane


def read(ctx):
    if ctx.trace is None or ctx.window is None or not ctx.trace.devices:
        return None
    lo, hi = ctx.window
    return 1.0 - xplane.busy_in(ctx.trace, lo, hi) / ((hi - lo) / 1e9)
