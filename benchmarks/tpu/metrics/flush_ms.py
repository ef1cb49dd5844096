"""Async server, result streaming: ``server.flush`` time (ms) per tick —
the window's ``server.flush`` spans summed and divided by its
``scheduler.tick`` spans.  The pump runs a flush after every tick and
holds the event loop through it (``AsyncServer._flush``)."""
from tpubench import spanargs


def read(ctx):
    return value(spanargs.of(ctx))


def value(spans):
    flushes = spanargs.named(spans, "server.flush")
    ticks = len(spanargs.named(spans, "scheduler.tick"))
    return sum(sp.ms for sp in flushes) / ticks if flushes and ticks \
        else None
