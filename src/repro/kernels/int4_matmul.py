"""Mixed-precision matmul Pallas kernel: bf16 activations × int4 weights
with Block-Floating-Point fixed-point accumulation (paper §4.2).

Per (M-tile row, K-group):
  1. the activation tile is converted to BFP — a shared power-of-2 exponent
     per row plus int8 mantissas (the paper's FP→BFP conversion);
  2. int8 × int4 products accumulate in **int32** (the fixed-point
     accumulation tree; on TPU this is the MXU's native int8 path — the
     throughput analogue of DSP overpacking, see DESIGN.md);
  3. one floating-point reconstruction per (row, group):
     acc_fp += acc_int · 2^(e_row - MBITS) · w_scale[group].

Weight codes are stored as int8 in [-8, 7] (int4 value domain); the dry-run
byte accounting treats them at 4 bits (DESIGN.md).  Scales are powers of 2
when cfg.quant.pow2_scales so step 3 is exponent arithmetic only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MBITS = 7          # int8 mantissa: values in [-128, 127], scale 2^7
DEFAULT_BM = 128
DEFAULT_BN = 128


def _bfp_quantize_rows(x: jnp.ndarray):
    """x: [bm, G] fp32 -> (mant int8 [bm, G], exp fp32 [bm, 1] = 2^e)."""
    amax = jnp.abs(x).max(axis=-1, keepdims=True)
    e = jnp.ceil(jnp.log2(jnp.maximum(amax, 1e-30)))
    e = jnp.where(amax == 0, 0.0, e)
    pe = jnp.exp2(e)
    mant = jnp.clip(jnp.round(x * (2.0 ** MBITS) / pe), -128, 127)
    return mant.astype(jnp.int8), pe


def int8_dot(mant: jnp.ndarray, codes: jnp.ndarray) -> jnp.ndarray:
    """[bm, G] int8 mantissas × [G, bn] int8 codes -> [bm, bn] int32:
    the MXU's native int8 path, accumulated in fixed point."""
    return jax.lax.dot_general(mant, codes.astype(jnp.int8),
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


def _int4_kernel(x_ref, w_ref, s_ref, o_ref, acc_scr, *, out_dtype):
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...].astype(jnp.float32)                    # [bm, G]
    mant, pe = _bfp_quantize_rows(x)
    prod = int8_dot(mant, w_ref[...])                     # [G, bn] codes
    scale = s_ref[pl.ds(k, 1), :]                         # [1, bn]
    acc_scr[...] += (prod.astype(jnp.float32)
                     * (pe * (2.0 ** -MBITS))             # [bm, 1]
                     * scale)                             # [1, bn]

    @pl.when(k == nk - 1)
    def _fin():
        o_ref[...] = acc_scr[...].astype(out_dtype)


def int4_matmul_pallas(x: jnp.ndarray, w_codes: jnp.ndarray,
                       scale: jnp.ndarray, *, bm: int = DEFAULT_BM,
                       bn: int = DEFAULT_BN,
                       interpret: bool = False) -> jnp.ndarray:
    """x: [M, K] (bf16/f32); w_codes: [K, N] int8 codes in [-8, 7];
    scale: [K/G, N] fp32.  Returns [M, N] in x.dtype."""
    M, K = x.shape
    Kw, N = w_codes.shape
    assert K == Kw
    G = K // scale.shape[0]
    assert K % G == 0
    bm = min(bm, M)
    bn = min(bn, N)
    Mp = -(-M // bm) * bm
    Np = -(-N // bn) * bn
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))
    if Np != N:
        w_codes = jnp.pad(w_codes, ((0, 0), (0, Np - N)))
        scale = jnp.pad(scale, ((0, 0), (0, Np - N)))

    grid = (Mp // bm, Np // bn, K // G)
    out = pl.pallas_call(
        functools.partial(_int4_kernel, out_dtype=x.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, G), lambda i, j, k: (i, k)),
            pl.BlockSpec((G, bn), lambda i, j, k: (k, j)),
            # the whole [K/G, bn] scale column stays resident; the kernel
            # reads its group's row (a (1, bn) block would break the
            # TPU's (8, 128) tiling)
            pl.BlockSpec((K // G, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w_codes, scale)
    return out[:M, :N]
