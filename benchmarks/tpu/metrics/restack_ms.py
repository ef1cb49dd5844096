"""Dense slot tick on the host: mean ``dense.restack`` span (ms) per
dispatch in the traced window — the ``np.zeros`` of the stacked tables
and planes and the copy of every slot's planes into them
(``DenseStepper.step``)."""
from tpubench import spanargs


def read(ctx):
    return value(spanargs.of(ctx))


def value(spans):
    return spanargs.mean_ms(spanargs.named(spans, "dense.restack"))
