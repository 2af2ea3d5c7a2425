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

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.int4_matmul import MBITS, _bfp_quantize_rows, int8_dot

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 512


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
                         act: Optional[str], has_res: bool, has_gmul: bool,
                         emit_sq: bool, eps: float, out_dtype):
    it = iter(refs)
    x_ref = next(it)
    ms_ref = next(it) if prologue else None
    g_ref = next(it) if prologue else None
    # one (weight, scale) pair per output half: [gate, up] under glu
    halves = 2 if glu else 1
    w_refs = [next(it) for _ in range(halves)]
    s_refs = [next(it) for _ in range(halves)] if int4 else None
    res_ref = next(it) if has_res else None
    gm_ref = next(it) if has_gmul else None
    o_ref = next(it)
    sq_ref = next(it) if emit_sq else None
    acc_scr = next(it)
    sq_scr = next(it) if emit_sq else None

    j = pl.program_id(1)
    k = pl.program_id(2)
    nj = pl.num_programs(1)
    nk = pl.num_programs(2)
    bn = o_ref.shape[-1]

    @pl.when(k == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    if emit_sq:
        @pl.when(jnp.logical_and(j == 0, k == 0))
        def _init_sq():
            sq_scr[...] = jnp.zeros_like(sq_scr)

    x = x_ref[...].astype(jnp.float32)                      # [bm, bk]
    if prologue:
        # RMSNorm elementwise phase from the injected reduction — the
        # normalized tile exists only in VMEM.
        x = x * jax.lax.rsqrt(ms_ref[...] + eps) \
              * g_ref[...].astype(jnp.float32)

    def halves_cat(parts):                                  # [.., halves·bn]
        return parts[0] if halves == 1 else jnp.concatenate(parts, axis=-1)

    w = halves_cat([r[...] for r in w_refs])                # [bk, halves·bn]
    if int4:
        mant, pe = _bfp_quantize_rows(x)                    # BFP domain
        prod = int8_dot(mant, w)                            # fixed point
        s = halves_cat([r[pl.ds(k, 1), :] for r in s_refs])  # [1, ·]
        acc_scr[...] += (prod.astype(jnp.float32)
                         * (pe * (2.0 ** -MBITS)) * s)
    else:
        acc_scr[...] += jax.lax.dot_general(
            x, w.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _fin():
        acc = acc_scr[...]
        if glu:
            y = _act(acc[:, :bn], act) * acc[:, bn:]
        else:
            y = _act(acc, act)
        if has_gmul:
            y = y * gm_ref[...]
        if has_res:
            y = y + res_ref[...].astype(jnp.float32)
        if emit_sq:
            sq_scr[...] += (y * y).sum(axis=-1, keepdims=True)
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
                        bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                        bk: int = DEFAULT_BK, interpret: bool = False
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
    output (the next block's norm reduction, pre-division)."""
    int4 = w_codes is not None
    assert (w is None) == int4, "exactly one of w / (w_codes, scale)"
    M, K = x.shape
    wt = w_codes if int4 else w
    Kw, N = wt.shape
    assert Kw >= K
    prologue = mean_sq is not None
    if prologue:
        assert gamma is not None

    if int4:
        rows = scale.shape[0]
        assert Kw % rows == 0, (Kw, rows)
        bk = Kw // rows                                     # K-tile == group
    else:
        bk = min(bk, Kw)

    F = N // 2 if glu else N
    bm = min(bm, M)
    bn = min(bn, F)
    Mp = -(-M // bm) * bm
    Fp = -(-F // bn) * bn
    Kp = -(-Kw // bk) * bk

    if Kp != Kw or Kp != K:
        x = jnp.pad(x, ((0, 0), (0, Kp - K)))
        if Kp != Kw:
            wt = jnp.pad(wt, ((0, Kp - Kw), (0, 0)))
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
    wb = 2 * bn if glu else bn
    nf = Fp // bn                   # column blocks per half

    in_specs = [pl.BlockSpec((bm, bk), lambda i, j, k: (i, k))]
    inputs = [x]
    if prologue:
        in_specs += [pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
                     pl.BlockSpec((1, bk), lambda i, j, k: (0, k))]
        inputs += [mean_sq.astype(jnp.float32)[:, None], gamma[None, :]]
    half_offsets = (0, nf) if glu else (0,)
    for off in half_offsets:
        in_specs.append(pl.BlockSpec(
            (bk, bn), lambda i, j, k, off=off: (k, j + off)))
        inputs.append(wt)
    if int4:
        # the whole [K/G, bn] scale column stays resident; the kernel
        # reads row k (a (1, bn) block would break the (8, 128) tiling)
        for off in half_offsets:
            in_specs.append(pl.BlockSpec(
                (scale.shape[0], bn), lambda i, j, k, off=off: (0, j + off)))
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

    scratch = [pltpu.VMEM((bm, wb), jnp.float32)]
    if emit_sq:
        scratch.append(pltpu.VMEM((bm, 1), jnp.float32))

    kernel = functools.partial(
        _fused_linear_kernel, prologue=prologue, int4=int4, glu=glu,
        act=act, has_res=residual is not None,
        has_gmul=gate_mul is not None, emit_sq=emit_sq, eps=eps,
        out_dtype=x.dtype)
    out = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        interpret=interpret)(*inputs)
    if emit_sq:
        return out[0][:M, :F], out[1][:M, 0]
    return out[0][:M, :F], None
