"""Fused attention Pallas kernel (TPU target; interpret=True on CPU).

Implements the paper's Alg. 2 dataflow on the TPU memory hierarchy:
  * online softmax features (running max m, running Σexp l) carried in VMEM
    scratch across KV tiles — the decoupled, incremental reduction;
  * **KV-head packing**: all G = Hq/Hkv query heads of one KV group are
    packed into the query-row dimension of a single grid cell, so each KV
    tile loaded from HBM is reused G× (the paper's multi-head packing,
    §3.2, re-targeted from DSP columns to MXU rows);
  * causal / sliding-window / valid-length masking by absolute position, so
    SkipGPT gather-mode (compacted query subsets) works unchanged.

Layouts: q [BH, R, dh] where BH = B·Hkv and R packs (G, Tq) rows;
k/v [BH, Tk, dh]; q_pos int32 [BH, R]; kv_len int32 [BH, 1].
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30
DEFAULT_BQ = 128
DEFAULT_BK = 128


def _flash_kernel(qpos_ref, kvlen_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, bk: int, causal: bool,
                  window: int, scale: float):
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale              # [bq, dh]
    k = k_ref[0].astype(jnp.float32)                      # [bk, dh]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [bq, bk]

    kv_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    q_pos = qpos_ref[0]                                   # [bq, 1]
    mask = kv_pos < kvlen_ref[0]                          # [1, 1] bcast
    if causal:
        mask &= kv_pos <= q_pos
    if window:
        mask &= kv_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                   # [bq, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                                # [bq, bk]
    alpha = jnp.exp(m_prev - m_new)                       # [bq, 1]
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
    v = v_ref[0].astype(jnp.float32)                      # [bk, dh]
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * alpha + pv
    m_scr[...] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-20)).astype(o_ref.dtype)


def flash_attention_packed(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           q_pos: jnp.ndarray, kv_len: jnp.ndarray, *,
                           causal: bool = True, window: int = 0,
                           scale: float, bq: int = DEFAULT_BQ,
                           bk: int = DEFAULT_BK,
                           interpret: bool = False) -> jnp.ndarray:
    """q: [BH, R, dh]; k/v: [BH, Tk, dh]; q_pos: [BH, R]; kv_len: [BH, 1]."""
    BH, R, dh = q.shape
    Tk = k.shape[1]
    bq = min(bq, R)
    bk = min(bk, Tk)

    # pad R and Tk to block multiples; padded q rows get position -1 (fully
    # masked -> guarded divide), padded kv masked via kv_len.
    Rp = -(-R // bq) * bq
    Tp = -(-Tk // bk) * bk
    if Rp != R:
        q = jnp.pad(q, ((0, 0), (0, Rp - R), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, Rp - R)), constant_values=-1)
    if Tp != Tk:
        k = jnp.pad(k, ((0, 0), (0, Tp - Tk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Tp - Tk), (0, 0)))

    # per-row vectors ride as [.., R, 1] / [.., 1, 1] columns so every
    # block's last two dims are whole array dims (TPU (8, 128) tiling)
    q_pos = q_pos[..., None]
    kv_len = kv_len.reshape(BH, 1, 1)
    grid = (BH, Rp // bq, Tp // bk)
    kernel = functools.partial(_flash_kernel, bk=bk, causal=causal,
                               window=window, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),    # q_pos
            pl.BlockSpec((1, 1, 1), lambda b, i, j: (b, 0, 0)),     # kv_len
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),   # q
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0)),   # k
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0)),   # v
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Rp, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # m
            pltpu.VMEM((bq, 1), jnp.float32),    # l
            pltpu.VMEM((bq, dh), jnp.float32),   # acc
        ],
        interpret=interpret,
    )(q_pos, kv_len, q, k, v)
    return out[:, :R]
