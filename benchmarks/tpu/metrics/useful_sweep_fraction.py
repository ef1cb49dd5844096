"""Dense superstep on the device: the share of the edge-states the
supersteps swept that a query needed.  The ``useful`` arguments of the
window's ``scheduler.retire`` spans (for each retired dense slot, the
in-degree summed over its visited (node, state) pairs) over the
``swept`` arguments of its ``dense.bfs_chunk`` spans (rows x edges x
state width x supersteps per dispatch).  A ratio of work counts, at
most 1 by construction; requests that straddle the window's edges bias
it by a few percent."""
from tpubench import spanargs


def read(ctx):
    return value(spanargs.of(ctx))


def value(spans):
    useful = spanargs.arg_values(spans, "scheduler.retire", "useful")
    swept = sum(spanargs.arg_values(spans, "dense.bfs_chunk", "swept"))
    return sum(useful) / swept if useful and swept else None
