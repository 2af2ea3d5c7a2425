"""Share of the KV entries the paged decode walks that its layer needs,
in %: over the window, the entries valid at the walking layer (one per
resident position) over every entry the attention reads, every slot's
block table up to the epoch's power-of-two width at every attention
layer.  The engine's ``kv_walk`` counter rows."""
from chipbench import engine_events


def read(ctx):
    d = engine_events.counter_delta(ctx, "kv_walk")
    if not d or not d.get("walked"):
        return None
    return 100.0 * d["valid"] / d["walked"]
