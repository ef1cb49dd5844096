"""Dense superstep: edge rows the chunk program still scatters, per
dispatch — the mean ``tail_rows`` argument of the window's
``dense.bfs_chunk`` spans: a live overlay's insert buffer, padded to a
power of two (0 without an overlay)."""
from tpubench import spanargs


def read(ctx):
    return value(spanargs.of(ctx))


def value(spans):
    rows = spanargs.arg_values(spans, "dense.bfs_chunk", "tail_rows")
    return sum(rows) / len(rows) if rows else None
