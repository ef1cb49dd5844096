"""Scheduler harvest and retirement: mean ``scheduler.harvest`` span
(ms) per tick in the traced window — the ``reported()`` sets, streamed
pairs and the retirement of converged slots (``SlotScheduler._harvest``)."""
from tpubench import spanargs


def read(ctx):
    return value(spanargs.of(ctx))


def value(spans):
    return spanargs.mean_ms(spanargs.named(spans, "scheduler.harvest"))
