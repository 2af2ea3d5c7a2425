"""Requests left queued while a slot stayed free, per decode dispatch of
the window: the engine's ``admission`` counter rows (counted once an
iteration after its planning, for either reason: one prefill admitted
per iteration, or too few free pages) over its ``dispatch`` spans."""
from chipbench import engine_events


def read(ctx):
    d = engine_events.counter_delta(ctx, "admission")
    dispatches = engine_events.engine_spans(ctx, "dispatch")
    if d is None or not dispatches:
        return None
    return sum(d.values()) / len(dispatches)
