"""Admission and planner: mean ``scheduler.admit`` span (ms) per admitted
query in the traced window (``SlotScheduler._admit_one``: cache probe,
parse, planner decision, slot admission or synchronous delegation)."""


def read(ctx):
    spans = ctx.host_spans("scheduler.admit")
    if not spans:
        return None
    return sum(e - s for s, e, _ in spans) / len(spans) / 1e6
