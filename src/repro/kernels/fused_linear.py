"""Unified fused linear-pipeline Pallas kernel family (paper Alg. 1 + §4.2).

One k-loop matmul kernel parameterized along three axes, so every linear
op of the routed block runs as a single VMEM-resident pipeline:

  * **prologue** — the RMSNorm elementwise phase applied to the activation
    tile *inside* the k-loop from injected ``mean_sq`` statistics
    (Alg. 1 ll. 11–15: the reduction was computed earlier, fused with the
    router; the normalized activation never round-trips through HBM).
  * **weight path** — dense bf16/f32, *or* int4 codes with per-group
    power-of-2 scales accumulated in the BFP fixed-point domain
    (paper §4.2): the (optionally normalized) activation tile feeds the
    FP→BFP row-quantization directly, then int8×int4 products accumulate
    in int32 with one FP reconstruction per (row, K-group).
  * **epilogue** — optional SwiGLU/GeGLU gating over a widened
    ``[gate | up]`` output (the ``[K, 2F]`` weight is fed twice, as the
    gate tile at column block j and the up tile at j + F/bn, so one grid
    cell holds both halves of an output block), optional per-row gate
    multiplier, optional residual add, and optional incremental emission
    of Σy² of the written residual stream — the *next* block's norm
    reduction (the paper's incremental-reduction carry) comes out of this
    kernel for free.

This subsumes the former ``rmsnorm_matmul`` kernel (prologue-only,
dense-only) and composes with the hybrid float-fixed path that the paper
actually deploys.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.int4_matmul import MBITS, _bfp_quantize_rows, int8_dot

LANE = 128                  # TPU lane width: tiles are multiples of it
ROW_ALIGN = 16              # bf16 sublane packing of a split row block
BM_MAX = 512                # rows per block: weights re-read per M/bm
BN_MAX = 2048               # output columns per block (per GLU half)
BK_MAX = 2048               # input rows per K step
W_TILE_MAX = 7 << 19        # weight bytes per half per grid step: 3.5 MiB
VMEM_BUDGET = 48 << 20      # scoped VMEM a planned call may ask for
VMEM_DEFAULT = 16 << 20     # the TPU compiler's default scoped limit
FALLBACK_BN, FALLBACK_BK = 128, 512   # padded tiles where no tile fits


class FusedLinearPlan(NamedTuple):
    """Tiles of one fused-linear call.  ``bn`` is per output half;
    ``fallback`` marks a plan that found no lane-aligned tile dividing
    an axis (it took the whole unaligned axis, or pads to 128-wide
    tiles); ``vmem_bytes`` is the estimate the scoped limit follows."""
    bm: int
    bn: int
    bk: int
    grid: Tuple[int, int, int]
    vmem_bytes: int
    fallback: bool

    @property
    def steps(self) -> int:
        return self.grid[0] * self.grid[1] * self.grid[2]


def _ceil(a: int, b: int) -> int:
    return -(-a // b)


def _tiles(total: int, unit: int, cap: int):
    """Tiles along an axis, largest first: multiples of ``unit`` and of
    the lane width that divide ``total``, up to ``cap``; the whole axis
    when it fits under ``cap``.  Never a tile that needs padding."""
    step = math.lcm(unit, LANE)
    out = {t for t in range(step, min(total, cap) + 1, step)
           if total % t == 0}
    if total <= cap:
        out.add(total)
    return sorted(out, reverse=True)


def _round(n: int, m: int) -> int:
    return _ceil(n, m) * m


def _vmem_bytes(bm: int, bn: int, bk: int, rows: int, halves: int,
                int4: bool, x_bytes: int, w_bytes: int) -> int:
    """VMEM a call holds: double-buffered x, weight, resident scale
    column, gamma, residual and output blocks; the f32 accumulator; the
    per-group int32/f32 products (int4) or the f32 weight copy (dense)
    as temporaries.  Counts every optional operand, so it bounds all
    epilogues.  Blocks are padded to (8, 128) tiles."""
    m8, n128, k128 = _round(bm, 8), _round(bn, LANE), _round(bk, LANE)
    b = 2 * m8 * k128 * x_bytes                             # x
    b += 2 * halves * bk * n128 * w_bytes                   # weight
    b += 2 * 8 * k128 * 4                                   # gamma
    b += 2 * 3 * m8 * LANE * 4                              # ms, gmul, sq
    b += 2 * 2 * m8 * n128 * x_bytes                        # out, residual
    b += halves * m8 * n128 * 4                             # accumulator
    if int4:
        b += 2 * halves * _round(rows, 8) * n128 * 4        # scales
        b += 2 * m8 * n128 * 4                              # products
    else:
        b += bk * n128 * 4 + m8 * k128 * 4
    return b


def fused_linear_plan(M: int, Kw: int, F: int, G: int, glu: bool,
                      int4: bool, *, x_bytes: int = 2,
                      w_bytes: int = 1) -> FusedLinearPlan:
    """Tiles of a call on ``M`` rows of an ``[Kw, (2·)F]`` weight (int4
    groups of ``G`` rows), chosen from the shape alone.

    Rows: all ``M`` in one block up to ``BM_MAX``, else the fewest
    blocks of at most ``BM_MAX`` (a multiple of 16; only x is padded).
    Columns and K: of the tiles whose sides divide ``Kw`` and ``F``
    (``bk`` a whole number of groups, both lane multiples or the whole
    axis), whose weight tile holds at most ``W_TILE_MAX`` bytes per half
    and whose VMEM stays under ``VMEM_BUDGET``, the widest ``bn``, then
    the deepest ``bk``.  Timed on a v5e (``tools/sweep_fused_linear.py``),
    a wider ``bn`` (fewer re-reads of x, fewer steps) is what pays; ``bk``
    beyond 512 rows hardly moves a call, and a 4 MiB tile that left a
    call 4 grid steps lost a fifth.  An axis with no such tile falls
    back to 128-wide column tiles or 512-row K steps (int4: one group),
    padding what does not divide."""
    unit = G if int4 else 1
    nb = _ceil(M, BM_MAX)
    bm = M if nb == 1 else _round(_ceil(M, nb), ROW_ALIGN)
    fb_bn = min(FALLBACK_BN, F)
    fb_bk = G if int4 else min(FALLBACK_BK, Kw)
    bns = _tiles(F, 1, BN_MAX) or [fb_bn]
    bks = _tiles(Kw, unit, BK_MAX) or [fb_bk]
    best = None
    for bk in bks:
        for bn in bns:
            if bk * bn * w_bytes > W_TILE_MAX:
                continue
            p = _tiles_plan(M, Kw, F, G, glu, int4, bm, bn, bk,
                            x_bytes=x_bytes, w_bytes=w_bytes)
            if p.vmem_bytes <= VMEM_BUDGET and (
                    best is None or (bn, bk) > (best.bn, best.bk)):
                best = p
    if best is None:
        best = _tiles_plan(M, Kw, F, G, glu, int4, bm, fb_bn, fb_bk,
                           x_bytes=x_bytes, w_bytes=w_bytes
                           )._replace(fallback=True)
    return best


def _tiles_plan(M: int, Kw: int, F: int, G: int, glu: bool, int4: bool,
                bm: int, bn: int, bk: int, *, x_bytes: int = 2,
                w_bytes: int = 1) -> FusedLinearPlan:
    """The plan of given tiles: its grid, its VMEM estimate (K padded to
    a ``bk`` multiple) and whether it falls back."""
    grid = (_ceil(M, bm), _ceil(F, bn), _ceil(Kw, bk))
    rows = grid[2] * bk // G if int4 else 1
    vm = _vmem_bytes(bm, bn, bk, rows, 2 if glu else 1, int4, x_bytes,
                     w_bytes)
    fallback = (bn % LANE != 0 or bk % LANE != 0 or F % bn != 0
                or Kw % bk != 0)
    return FusedLinearPlan(bm, bn, bk, grid, vm, fallback)


_RECORD: contextvars.ContextVar = contextvars.ContextVar(
    "fused_linear_plans", default=None)


@contextlib.contextmanager
def recording(plans: Dict[Tuple[str, str], FusedLinearPlan], phase: str):
    """Within this context every fused-linear call that traces adds its
    plan to ``plans`` under ``("MxKxN", phase)``: once per compiled call
    shape, never per step."""
    token = _RECORD.set((plans, phase))
    try:
        yield
    finally:
        _RECORD.reset(token)


def _act(x: jnp.ndarray, act: Optional[str]) -> jnp.ndarray:
    """Epilogue activation dispatch — shared with ref.fused_linear_ref so
    the oracle and the kernel can never diverge on a new activation."""
    if act is None:
        return x
    if act == "silu":
        return jax.nn.silu(x)
    if act == "gelu":
        return jax.nn.gelu(x)
    raise ValueError(f"unknown epilogue activation {act!r}")


def _fused_linear_kernel(*refs, prologue: bool, int4: bool, glu: bool,
                         joint: bool, group: int, act: Optional[str],
                         has_res: bool, has_gmul: bool, emit_sq: bool,
                         eps: float, out_dtype):
    it = iter(refs)
    x_ref = next(it)
    ms_ref = next(it) if prologue else None
    g_ref = next(it) if prologue else None
    # one (weight, scale) pair per output half ([gate, up] under glu),
    # or one pair holding both halves side by side (``joint``)
    n_w = 2 if glu and not joint else 1
    w_refs = [next(it) for _ in range(n_w)]
    s_refs = [next(it) for _ in range(n_w)] if int4 else None
    res_ref = next(it) if has_res else None
    gm_ref = next(it) if has_gmul else None
    o_ref = next(it)
    sq_ref = next(it) if emit_sq else None
    acc_scr = next(it)                                      # [n_w, bm, ·]
    sq_scr = next(it) if emit_sq else None

    j = pl.program_id(1)
    k = pl.program_id(2)
    nj = pl.num_programs(1)
    nk = pl.num_programs(2)
    bn = o_ref.shape[-1]
    bk = x_ref.shape[-1]

    @pl.when(k == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    if emit_sq:
        @pl.when(jnp.logical_and(j == 0, k == 0))
        def _init_sq():
            sq_scr[...] = jnp.zeros_like(sq_scr)

    # RMSNorm elementwise phase from the injected reduction: the
    # normalized tile exists only in VMEM
    inv = jax.lax.rsqrt(ms_ref[...] + eps) if prologue else None

    def x_cols(lo, n: int) -> jnp.ndarray:                  # [bm, n] f32
        x = x_ref[:, pl.ds(lo, n)].astype(jnp.float32)
        if prologue:
            x = x * inv * g_ref[:, pl.ds(lo, n)].astype(jnp.float32)
        return x

    if int4:
        # the K step spans bk / group quantization groups, taken in
        # order: per (row, group) one BFP quantization, an int8 dot in
        # fixed point and one FP reconstruction, as with one group a step
        n_g = bk // group

        def group_step(g, carry):
            lo = pl.multiple_of(g * group, group)
            mant, pe = _bfp_quantize_rows(x_cols(lo, group))
            pe = pe * (2.0 ** -MBITS)
            for h in range(n_w):
                prod = int8_dot(mant, w_refs[h][pl.ds(lo, group), :])
                s = s_refs[h][pl.ds(k * n_g + g, 1), :]     # [1, ·]
                acc_scr[h] += prod.astype(jnp.float32) * pe * s
            return carry

        jax.lax.fori_loop(0, n_g, group_step, 0)
    else:
        x = x_cols(0, bk)
        for h in range(n_w):
            acc_scr[h] += jax.lax.dot_general(
                x, w_refs[h][...].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _fin():
        if not glu:
            y = _act(acc_scr[0], act)
        elif joint:
            acc = acc_scr[0]
            y = _act(acc[:, :bn], act) * acc[:, bn:]
        else:
            y = _act(acc_scr[0], act) * acc_scr[1]
        if has_gmul:
            y = y * gm_ref[...]
        if has_res:
            y = y + res_ref[...].astype(jnp.float32)
        if emit_sq:
            # Σy² in 128-column chunks, in order: the same sums and the
            # same order of adding them whatever bn is
            for c in range(0, bn, LANE):
                yc = y[:, c:c + LANE]
                sq_scr[...] += (yc * yc).sum(axis=-1, keepdims=True)
            @pl.when(j == nj - 1)
            def _emit():
                sq_ref[...] = sq_scr[...]
        o_ref[...] = y.astype(out_dtype)


def fused_linear_pallas(x: jnp.ndarray, w: Optional[jnp.ndarray] = None,
                        w_codes: Optional[jnp.ndarray] = None,
                        scale: Optional[jnp.ndarray] = None, *,
                        mean_sq: Optional[jnp.ndarray] = None,
                        gamma: Optional[jnp.ndarray] = None,
                        eps: float = 1e-5,
                        glu: bool = False, act: Optional[str] = None,
                        residual: Optional[jnp.ndarray] = None,
                        gate_mul: Optional[jnp.ndarray] = None,
                        emit_sq: bool = False,
                        bm: Optional[int] = None, bn: Optional[int] = None,
                        bk: Optional[int] = None, interpret: bool = False
                        ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """x: [M, K] × weight [K', N] -> (out [M, F], Σy² [M] f32 or None).

    Exactly one of ``w`` (dense) or ``(w_codes, scale)`` (int4 codes in
    [-8, 7] stored as int8; scale [K'/G, N]) must be given.  ``K' >= K``
    covers group-padded quantized weights (the trailing rows are zero
    codes); x is zero-padded up to K'.  With ``glu`` the weight is the
    widened ``[gate | up]`` matrix (N == 2F) and the output is
    ``act(x·Wg) * (x·Wu)`` of width F; otherwise F == N and ``act`` (if
    any) applies elementwise.  ``mean_sq`` [M] + ``gamma`` [K] enable the
    RMSNorm prologue; ``gate_mul`` [M] scales rows before the optional
    ``residual`` [M, F] add; ``emit_sq`` returns Σy² per row of the final
    output (the next block's norm reduction, pre-division).

    The tiles come from ``fused_linear_plan``; ``bm``/``bn``/``bk``
    override them (tests compare tilings through them; an int4 ``bk`` is
    a multiple of the group)."""
    int4 = w_codes is not None
    assert (w is None) == int4, "exactly one of w / (w_codes, scale)"
    M, K = x.shape
    wt = w_codes if int4 else w
    Kw, N = wt.shape
    assert Kw >= K
    prologue = mean_sq is not None
    if prologue:
        assert gamma is not None
    rows = scale.shape[0] if int4 else 1
    assert Kw % rows == 0, (Kw, rows)
    G = Kw // rows
    F = N // 2 if glu else N

    nbytes = dict(x_bytes=x.dtype.itemsize, w_bytes=wt.dtype.itemsize)
    plan = fused_linear_plan(M, Kw, F, G, glu, int4, **nbytes)
    if bm or bn or bk:
        plan = _tiles_plan(M, Kw, F, G, glu, int4, min(bm or plan.bm, M),
                           min(bn or plan.bn, F), min(bk or plan.bk, Kw),
                           **nbytes)
    rec = _RECORD.get()
    if rec is not None:
        rec[0][(f"{M}x{Kw}x{N}", rec[1])] = plan
    bm, bn, bk = plan.bm, plan.bn, plan.bk
    if int4:
        assert bk % G == 0, (bk, G)
    # a [gate | up] pair whose halves are not lane-aligned tiles rides one
    # block of the whole weight width (``joint``): padded to 128-wide
    # tiles instead, the dense dot of such a pair misses the oracle's
    # bound (test_fused_linear_matches_oracle_grid, the F = 130 GLU case)
    joint = glu and bn % LANE != 0
    assert not joint or bn == F, (bn, F)
    Mp = -(-M // bm) * bm
    Fp = -(-F // bn) * bn
    Kp = -(-Kw // bk) * bk

    if Kp != Kw or Kp != K:
        x = jnp.pad(x, ((0, 0), (0, Kp - K)))
        if Kp != Kw:
            wt = jnp.pad(wt, ((0, Kp - Kw), (0, 0)))
            if int4:
                scale = jnp.pad(scale, ((0, (Kp - Kw) // G), (0, 0)))
        if prologue:
            gamma = jnp.pad(gamma, (0, Kp - K))
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))
        if prologue:
            mean_sq = jnp.pad(mean_sq, (0, Mp - M), constant_values=1.0)
        if residual is not None:
            residual = jnp.pad(residual, ((0, Mp - M), (0, 0)))
        if gate_mul is not None:
            gate_mul = jnp.pad(gate_mul, (0, Mp - M))
    if Fp != F:
        # pad each output half ([gate | up] under glu) to a bn multiple
        halves = 2 if glu else 1

        def pad_cols(a):
            r = a.shape[0]
            return jnp.pad(a.reshape(r, halves, F),
                           ((0, 0), (0, 0), (0, Fp - F))
                           ).reshape(r, halves * Fp)

        wt = pad_cols(wt)
        if int4:
            scale = pad_cols(scale)
        if residual is not None:
            residual = jnp.pad(residual, ((0, 0), (0, Fp - F)))

    grid = (Mp // bm, Fp // bn, Kp // bk)
    nf = Fp // bn                   # column blocks per half
    wb = 2 * bn if joint else bn    # weight block width
    half_offsets = (0, nf) if glu and not joint else (0,)

    in_specs = [pl.BlockSpec((bm, bk), lambda i, j, k: (i, k))]
    inputs = [x]
    if prologue:
        in_specs += [pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
                     pl.BlockSpec((1, bk), lambda i, j, k: (0, k))]
        inputs += [mean_sq.astype(jnp.float32)[:, None], gamma[None, :]]
    for off in half_offsets:
        in_specs.append(pl.BlockSpec(
            (bk, wb), lambda i, j, k, off=off: (k, j + off)))
        inputs.append(wt)
    if int4:
        # the whole [K/G, ·] scale column stays resident; the kernel
        # reads its groups' rows (a (1, ·) block would break the (8, 128)
        # tiling)
        for off in half_offsets:
            in_specs.append(pl.BlockSpec(
                (scale.shape[0], wb), lambda i, j, k, off=off: (0, j + off)))
            inputs.append(scale)
    if residual is not None:
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)))
        inputs.append(residual)
    if gate_mul is not None:
        in_specs.append(pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)))
        inputs.append(gate_mul.astype(jnp.float32)[:, None])

    out_specs = [pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))]
    out_shape = [jax.ShapeDtypeStruct((Mp, Fp), x.dtype)]
    if emit_sq:
        out_specs.append(pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((Mp, 1), jnp.float32))

    scratch = [pltpu.VMEM((len(half_offsets), bm, wb), jnp.float32)]
    if emit_sq:
        scratch.append(pltpu.VMEM((bm, 1), jnp.float32))

    params = None
    if plan.vmem_bytes > VMEM_DEFAULT:
        # raise the scoped limit only as far as the estimate needs
        params = pltpu.CompilerParams(
            vmem_limit_bytes=_ceil(plan.vmem_bytes * 5 // 4, 1 << 20) << 20)

    kernel = functools.partial(
        _fused_linear_kernel, prologue=prologue, int4=int4, glu=glu,
        joint=joint, group=G, act=act, has_res=residual is not None,
        has_gmul=gate_mul is not None, emit_sq=emit_sq, eps=eps,
        out_dtype=x.dtype)
    out = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        compiler_params=params, interpret=interpret)(*inputs)
    if emit_sq:
        return out[0][:M, :F], out[1][:M, 0]
    return out[0][:M, :F], None
