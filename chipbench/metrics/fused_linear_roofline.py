"""Least time of the window's fused-linear calls at the chip's peaks over
the kernel's device time, in %.  Per call: 2*M*K*N operations at the
int8 peak; int4 codes at half a byte, their scales, the activations in
and out (and the residual) at HBM bandwidth.  Only blocks whose router
leans to keeping count, and M is the rows they keep: of the residents
of each decode step, and of the real prompt tokens of each prefill.  The
calls and their counts are the configuration's family's."""
from chipbench.trace import TPU_KERNEL

LABEL = "fused_linear"


def matches(text: str, dims) -> bool:
    """The kernel's op, as the trace names it by its HLO instruction: a
    Pallas call (tpu_custom_call) with int8-stored int4 codes among its
    operands, other than the output head's int4 matmul (codes
    ``s8[d_model,vocab]``)."""
    return (TPU_KERNEL in text and "s8[" in text
            and f"s8[{dims.d},{dims.vocab}]" not in text)


def read(ctx):
    if ctx.trace is None or ctx.keep is None:
        return None
    dev = sum(v for k, v in ctx.trace["ops"].items()
              if matches(k, ctx.dims))
    if dev <= 0:
        return None
    calls = [(e.n, e.residents) for e in ctx.epochs]
    calls += [(1, tokens) for tokens, _ in ctx.prefills]
    fam = ctx.family
    kept = fam.kept_linears(ctx.dims, ctx.lean)
    shares = fam.row_share(ctx.dims, ctx.lean, ctx.keep)
    least = 0.0
    for times, m in calls:
        for (lin, layers), share in zip(kept, shares):
            ops, byts = fam.linear_call(lin, m * share)
            least += times * layers * max(
                ops / ctx.peaks["int8_ops"],
                byts / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / dev
