"""Share of the KV entries the paged decode walks that lie in an active
slot's chain, in %: over the window, the stored entries of the slots
active in each step (every layer's) over every entry the attention
reads.  What it leaves out is the walk over empty slots and over the
padding up to the block table's power-of-two width; ``live`` over
``valid`` is the walk over other layers' entries.  The engine's
``kv_walk`` counter rows."""
from chipbench import engine_events


def read(ctx):
    d = engine_events.counter_delta(ctx, "kv_walk")
    if not d or not d.get("walked"):
        return None
    return 100.0 * d["live"] / d["walked"]
