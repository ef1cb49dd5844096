"""Dense superstep on the device: per dispatch of the slot tick's program
(``_bfs_chunk_hetero``), the device-busy time of its operations (ms),
from the profiler's ``XLA Modules`` and ``XLA Ops`` lines."""
from tpubench import xplane

PROGRAM = "bfs_chunk_hetero"


def read(ctx):
    if ctx.trace is None or ctx.window is None:
        return None
    lo, hi = ctx.window
    per = []
    for dev in ctx.trace.devices:
        for s, e, name in dev.modules:
            if PROGRAM in name and s >= lo and e <= hi:
                per.append(xplane.union(dev.ops, s, e))
    return sum(per) / len(per) * 1e3 if per else None
