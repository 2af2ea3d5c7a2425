"""Least time of the window's paged-attention calls at the chip's peaks
over the kernel's device time, in %.  A call is one layer of one decode
step, and counts for the share of its queries whose gate is open (the
share of attention gates the reference opens on this run's sample):
the entries valid at that layer of each attending resident read once, q
and the output, and the QK and PV dots at the bf16 peak, as the
configuration's family counts them."""
import re

from chipbench.trace import TPU_KERNEL

LABEL = "paged_attention"
# The kernel's op, as the trace names it by its HLO instruction: a Pallas
# call (tpu_custom_call) returning the online-softmax triple (acc, m, l)
# in float32 whose first operand is the int32 block table.
_OP = re.compile(r" = \(f32\[[^=]*\) custom-call\(s32\[")


def matches(text: str, dims=None) -> bool:
    return TPU_KERNEL in text and bool(_OP.search(text))


def read(ctx):
    if ctx.trace is None or ctx.keep is None or not ctx.epochs:
        return None
    dev = sum(v for k, v in ctx.trace["ops"].items() if matches(k))
    if dev <= 0:
        return None
    calls = ctx.keep[0] * ctx.dims.layers       # per step, at full share
    least = 0.0
    for e in ctx.epochs:
        ops, byts = ctx.family.paged_attention_call(
            ctx.dims, e.ctx_sum + e.residents * (e.n - 1) / 2, e.residents,
            1.0)
        least += e.n * calls * max(ops / ctx.peaks["bf16_flops"],
                                   byts / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / dev
