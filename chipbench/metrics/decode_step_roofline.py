"""Least time of the window's decode steps at the chip's peaks (the
weights of the blocks whose routers lean to keeping and every resident's
stored KV entries read once per step, operations at the int8 peak) over
the device time of the decode-epoch programs, in %.  The least time is
the configuration's family's."""
from chipbench.trace import seconds_of

# program (XLA module) name of the engine's fused paged decode epoch
PROGRAMS = ("jit_loop_fn",)


def read(ctx):
    if ctx.trace is None or ctx.keep is None or not ctx.epochs:
        return None
    dev = seconds_of(ctx.trace["modules"], PROGRAMS)
    if dev <= 0:
        return None
    ak, mk = ctx.keep
    per_token = 1 + ak * (ctx.dims.layers - 1)   # entries a step stores
    least = 0.0
    for e in ctx.epochs:
        mid = e.residents * (e.n - 1) / 2        # steps into the epoch
        least += e.n * ctx.family.decode_step_least_s(
            ctx.dims, ctx.peaks, e.residents, e.entries + mid * per_token,
            e.ctx_sum + mid, ak, mk, ctx.lean)
    return 100.0 * least / dev
