"""Device milliseconds of the prefill programs per thousand prompt
tokens prefilled in the window."""
from chipbench.trace import seconds_of

# program (XLA module) names of the engine's paged prefill
PROGRAMS = ("_prefill_paged_fn",)


def read(ctx):
    tokens = sum(t for t, _ in ctx.prefills)
    if ctx.trace is None or not tokens:
        return None
    dev = seconds_of(ctx.trace["modules"], PROGRAMS)
    return dev / tokens * 1e6 if dev > 0 else None
