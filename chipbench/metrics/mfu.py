"""Operations the routed model needs for every prompt token prefilled
and every output token decoded in the window, over the window times the
chip's bf16 peak, in %.  Submodules the routers skipped do no work; the
shares kept are the reference's on this run's sample.  The counts are
the configuration's family's."""


def read(ctx):
    if ctx.trace is None or ctx.keep is None:
        return None
    fam, dm, (ak, mk) = ctx.family, ctx.dims, ctx.keep
    ops = 0.0
    for r in ctx.reqs:
        p = len(r.prompt)
        for j, t in enumerate(r.times):
            if not ctx.t_open <= t <= ctx.t_close:
                continue
            if j == 0:      # the prefill that produced the first token
                ops += sum(fam.token_flops(dm, i + 1, ak, mk, False)
                           for i in range(p))
                ops += fam.prefill_logit_ops(dm)
            else:
                ops += fam.token_flops(dm, p + j, ak, mk, True)
    if not ops:
        return None
    return 100.0 * ops / (ctx.trace["window_s"] * ctx.peaks["bf16_flops"])
