"""Host-device transfer: mean ``dense.upload`` plus mean
``dense.download`` span (ms) per dispatch in the traced window — the
upload of the stacked tables and planes, and the download of the
frontier and visited planes with their write-back into the slots."""
from tpubench import spanargs


def read(ctx):
    return value(spanargs.of(ctx))


def value(spans):
    up = spanargs.mean_ms(spanargs.named(spans, "dense.upload"))
    down = spanargs.mean_ms(spanargs.named(spans, "dense.download"))
    return None if up is None or down is None else up + down
