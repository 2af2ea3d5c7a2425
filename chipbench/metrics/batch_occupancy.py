"""Mean residents per decode dispatch over the slots, in %: the engine's
scheduler state read before each iteration that dispatched an epoch."""


def read(ctx):
    occ = [s.residents for s in ctx.snaps if s.dispatched]
    if not occ:
        return None
    return 100.0 * sum(occ) / len(occ) / ctx.slots
