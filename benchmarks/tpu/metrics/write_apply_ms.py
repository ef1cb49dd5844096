"""Live updates: mean ``updates.apply`` span (ms) per write batch in the
traced window (``delta.apply_engine_updates``: the overlay's apply, the
result-cache and plan-decision invalidation, the dense engine's rebuild
and upload of its effective edge rows, the planner statistics' refresh
and, past the threshold, compaction)."""
from tpubench import spanargs


def read(ctx):
    return value(spanargs.of(ctx))


def value(spans):
    return spanargs.mean_ms(spanargs.named(spans, "updates.apply"))
