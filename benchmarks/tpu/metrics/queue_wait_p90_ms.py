"""Admission queue: 90th percentile of the ``queue_wait_ms`` argument
(submission to slot admission, on the scheduler's clock) over the
window's ``scheduler.admit`` spans."""
from tpubench import harness, spanargs


def read(ctx):
    return value(spanargs.of(ctx))


def value(spans):
    return harness.pct(
        spanargs.arg_values(spans, "scheduler.admit", "queue_wait_ms"), 90)
