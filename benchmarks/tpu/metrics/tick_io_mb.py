"""Host-device transfer: bytes moved per ``scheduler.tick`` (MB, 1e6
bytes), the ``bytes`` arguments of the window's ``dense.upload`` and
``dense.download`` spans summed and divided by its ticks."""
from tpubench import spanargs


def read(ctx):
    return value(spanargs.of(ctx))


def value(spans):
    moved = spanargs.arg_values(spans, "dense.upload", "bytes") \
        + spanargs.arg_values(spans, "dense.download", "bytes")
    ticks = len(spanargs.named(spans, "scheduler.tick"))
    return sum(moved) / ticks / 1e6 if moved and ticks else None
