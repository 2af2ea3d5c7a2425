"""Attention: GQA projections (RoPE/M-RoPE, qk-norm) + chunked online-softmax
attention.

The chunked scan is the XLA realization of the paper's Alg. 2 (deep-fused
self-attention): softmax row statistics (running max, running Σexp) are
accumulated *incrementally per KV tile* so no full attention row is ever
materialized — identical update rule to FlashAttention, which the paper
itself adopts.  The Pallas kernel in ``repro/kernels/flash_attention.py`` is
the TPU-tiled version of the same dataflow; this module is the pure-jnp
path XLA can fuse (and the oracle the kernel is tested against).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import hint
from repro.models import layers
from repro.models.layers import Params

NEG_INF = -1.0e30


# ---------------------------------------------------------------------------
# Projections
# ---------------------------------------------------------------------------

def attention_init(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 2)
    d, ai, ki = cfg.d_model, cfg.attn_inner_dim, cfg.kv_inner_dim
    p: Params = {
        # widened [q | k | v] projection: one k-loop serves all three
        # (the fused norm-prologue then runs once per block, not thrice)
        "wqkv": layers.linear_init(ks[0], d, ai + 2 * ki, cfg),
        "wo": layers.linear_init(ks[1], ai, d, cfg),
    }
    if cfg.qk_norm:
        p["qnorm"] = layers.rms_head_norm_init(cfg.resolved_head_dim, cfg)
        p["knorm"] = layers.rms_head_norm_init(cfg.resolved_head_dim, cfg)
    return p


def _wq(params: Params, cfg: ModelConfig) -> Params:
    if "wqkv" in params:
        return layers.slice_linear(params["wqkv"], 0, cfg.attn_inner_dim)
    return params["wq"]                                   # legacy split


def _wkv(params: Params, cfg: ModelConfig) -> Tuple[Params, Params]:
    ai, ki = cfg.attn_inner_dim, cfg.kv_inner_dim
    if "wqkv" in params:
        return (layers.slice_linear(params["wqkv"], ai, ai + ki),
                layers.slice_linear(params["wqkv"], ai + ki, ai + 2 * ki))
    return params["wk"], params["wv"]                     # legacy split


def _finish_q(params, q, positions, cfg: ModelConfig) -> jnp.ndarray:
    B, T = q.shape[:2]
    q = q.reshape(B, T, cfg.num_heads, cfg.resolved_head_dim)
    if cfg.qk_norm:
        q = layers.rms_head_norm(params["qnorm"], q, cfg.norm_eps)
    return layers.apply_rope(q, positions, cfg)


def _finish_kv(params, k, v, positions, cfg: ModelConfig):
    B, T = k.shape[:2]
    k = k.reshape(B, T, cfg.num_kv_heads, cfg.resolved_head_dim)
    v = v.reshape(B, T, cfg.num_kv_heads, cfg.resolved_head_dim)
    if cfg.qk_norm:
        k = layers.rms_head_norm(params["knorm"], k, cfg.norm_eps)
    k = layers.apply_rope(k, positions, cfg)
    return k, v


def project_q(params: Params, x: jnp.ndarray, positions: jnp.ndarray,
              cfg: ModelConfig, *, norm: Optional[Params] = None,
              stats: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """x: [B, T, D] -> q: [B, T, Hq, dh] (rope'd, qk-normed).

    With ``norm``/``stats`` the RMSNorm elementwise phase fuses into the
    projection's k-loop (x is un-normalized; stats is the injected
    reduction).  Without them x must already be normalized."""
    if norm is not None and layers.fuse_norm_linear(cfg):
        q, _ = layers.linear_fused(_wq(params, cfg), x, cfg,
                                   norm=norm, stats=stats)
    else:
        if norm is not None:
            x = layers.norm_apply(norm, x, cfg, stats=stats)
        q = layers.linear_apply(_wq(params, cfg), x, cfg)
    return _finish_q(params, q, positions, cfg)


def project_kv(params: Params, x: jnp.ndarray, positions: jnp.ndarray,
               cfg: ModelConfig, *, norm: Optional[Params] = None,
               stats: Optional[jnp.ndarray] = None
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [B, T, D] -> (k, v): [B, T, Hkv, dh].  K is stored post-RoPE so that
    cross-layer KV reuse (paper §2.1) inherits rotated keys unchanged.
    ``norm``/``stats`` fuse the norm prologue as in ``project_q``."""
    wk, wv = _wkv(params, cfg)
    if norm is not None and layers.fuse_norm_linear(cfg):
        ki = cfg.kv_inner_dim
        if "wqkv" in params:
            ai = cfg.attn_inner_dim
            wkv = layers.slice_linear(params["wqkv"], ai, ai + 2 * ki)
            kv, _ = layers.linear_fused(wkv, x, cfg, norm=norm, stats=stats)
            k, v = kv[..., :ki], kv[..., ki:]
        else:
            # legacy split weights: two prologue-fused calls (a merged
            # view would re-concatenate the weights on every step)
            k, _ = layers.linear_fused(wk, x, cfg, norm=norm, stats=stats)
            v, _ = layers.linear_fused(wv, x, cfg, norm=norm, stats=stats)
    else:
        if norm is not None:
            x = layers.norm_apply(norm, x, cfg, stats=stats)
        k = layers.linear_apply(wk, x, cfg)
        v = layers.linear_apply(wv, x, cfg)
    return _finish_kv(params, k, v, positions, cfg)


def project_qkv(params: Params, x: jnp.ndarray, positions: jnp.ndarray,
                cfg: ModelConfig, *, norm: Optional[Params] = None,
                stats: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Single widened projection producing q, k, v in one k-loop pass —
    with ``norm``/``stats``, the normalized activation lives only in VMEM
    (Alg. 1 prologue fusion; composes with int4-BFP weights)."""
    ai, ki = cfg.attn_inner_dim, cfg.kv_inner_dim
    if "wqkv" not in params:                              # legacy split
        q = project_q(params, x, positions, cfg, norm=norm, stats=stats)
        k, v = project_kv(params, x, positions, cfg, norm=norm, stats=stats)
        return q, k, v
    if norm is not None and layers.fuse_norm_linear(cfg):
        qkv, _ = layers.linear_fused(params["wqkv"], x, cfg,
                                     norm=norm, stats=stats)
    else:
        if norm is not None:
            x = layers.norm_apply(norm, x, cfg, stats=stats)
        qkv = layers.linear_apply(params["wqkv"], x, cfg)
    q = _finish_q(params, qkv[..., :ai], positions, cfg)
    k, v = _finish_kv(params, qkv[..., ai:ai + ki], qkv[..., ai + ki:],
                      positions, cfg)
    return q, k, v


def output_proj(params: Params, o: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    B, T = o.shape[:2]
    o = hint(o.reshape(B, T, cfg.attn_inner_dim), "contracted")
    return layers.linear_apply(params["wo"], o, cfg)


def output_proj_fused(params: Params, o: jnp.ndarray, cfg: ModelConfig, *,
                      residual: jnp.ndarray,
                      gate_mul: Optional[jnp.ndarray] = None,
                      emit_sq: bool = False):
    """Fused o-projection epilogue: y = (o·Wo)·gate + residual in one
    kernel, optionally emitting Σy² of the written residual stream — the
    next block's norm reduction (incremental-reduction carry).  Returns
    (new residual stream, Σy²|None)."""
    B, T = o.shape[:2]
    return layers.linear_fused(
        params["wo"], o.reshape(B, T, cfg.attn_inner_dim), cfg,
        residual=residual, gate_mul=gate_mul, emit_sq=emit_sq)


# ---------------------------------------------------------------------------
# Chunked online-softmax attention (Alg. 2 dataflow)
# ---------------------------------------------------------------------------

def _mask_for_chunk(q_pos: jnp.ndarray, kv_pos: jnp.ndarray, *, causal: bool,
                    window: int, kv_valid_len: Optional[jnp.ndarray],
                    batch: int) -> jnp.ndarray:
    """Boolean [B, Tq, Ck] mask (True = attend).  kv_pos is [Ck] (shared) or
    [B, Ck] (per-sequence — ragged decode over ring/slot caches)."""
    qp = q_pos[:, :, None]           # [B, Tq, 1]
    kp = kv_pos[:, None, :] if kv_pos.ndim == 2 else kv_pos[None, None, :]
    m = jnp.ones((batch, q_pos.shape[1], kv_pos.shape[-1]), bool)
    if causal:
        m &= kp <= qp
    if window:
        m &= kp > qp - window
    if kv_valid_len is not None:
        m &= kp < kv_valid_len[:, None, None]
    return m


def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      q_positions: jnp.ndarray,
                      causal: bool = True,
                      window: int = 0,
                      kv_valid_len: Optional[jnp.ndarray] = None,
                      chunk: int = 1024,
                      softmax_scale: Optional[float] = None,
                      kv_positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Online-softmax attention over KV chunks.

    q: [B, Tq, Hq, dh] — Tq may be a *gathered subset* of positions (SkipGPT
       gather mode); ``q_positions`` [B, Tq] carries original indices for the
       causal/window masks.
    k, v: [B, Tk, Hkv, dh] — the (possibly reused) per-layer KV view.
    kv_positions: optional explicit [Tk] or [B, Tk] absolute positions
       (ring-buffer caches; per-sequence for ragged decode); default
       arange(Tk).
    Returns [B, Tq, Hq, dh].
    """
    B, Tq, Hq, dh = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(dh)

    # scale in fp32, then back to the storage dtype: the QK/PV dots run on
    # bf16 operands with fp32 accumulation (preferred_element_type) so the
    # KV cache is never materialized in fp32 (2× HBM traffic otherwise).
    qT = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qT = qT.reshape(B, Tq, Hkv, G, dh).transpose(0, 2, 3, 1, 4)  # [B,Hkv,G,Tq,dh]

    chunk = min(chunk, Tk)
    pad = (-Tk) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if kv_positions is not None:
            pads = [(0, 0)] * (kv_positions.ndim - 1) + [(0, pad)]
            kv_positions = jnp.pad(kv_positions, pads,
                                   constant_values=jnp.iinfo(jnp.int32).max)
        elif kv_valid_len is None:
            # padded tail masked via kv_valid_len
            kv_valid_len = jnp.full((B,), Tk, jnp.int32)
    nc = k.shape[1] // chunk
    kc = k.transpose(1, 0, 2, 3).reshape(nc, chunk, B, Hkv, dh)
    vc = v.transpose(1, 0, 2, 3).reshape(nc, chunk, B, Hkv, dh)

    m0 = jnp.full((B, Hkv, G, Tq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Tq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, Tq, dh), jnp.float32)

    def body(carry, inp):
        m, l, acc = carry
        ci, k_c, v_c = inp
        k_c = k_c.transpose(1, 0, 2, 3)                    # [B,chunk,Hkv,dh]
        v_c = v_c.transpose(1, 0, 2, 3)
        s = jnp.einsum("bhgqd,bkhd->bhgqk", qT, k_c,
                       preferred_element_type=jnp.float32)
        if kv_positions is not None:
            kv_pos = jax.lax.dynamic_slice_in_dim(
                kv_positions, ci * chunk, chunk, axis=kv_positions.ndim - 1)
        else:
            kv_pos = ci * chunk + jnp.arange(chunk)
        mask = _mask_for_chunk(q_positions, kv_pos, causal=causal,
                               window=window, kv_valid_len=kv_valid_len,
                               batch=B)                     # [B,Tq,chunk]
        s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(v_c.dtype), v_c,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    if nc == 1:
        (m, l, acc), _ = body((m0, l0, a0), (jnp.int32(0), kc[0], vc[0]))
    else:
        (m, l, acc), _ = jax.lax.scan(
            body, (m0, l0, a0), (jnp.arange(nc), kc, vc))

    out = acc / jnp.maximum(l, 1e-20)[..., None]            # [B,Hkv,G,Tq,dh]
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Tq, Hq, dh)
    return out.astype(q.dtype)


def decode_attention_bhtd(q, k, v, *, q_positions, cfg: ModelConfig,
                          kv_valid_len=None) -> jnp.ndarray:
    """Single-token attention against a head-major [B, Hkv, T, dh] cache —
    the dots consume the cache directly (no per-layer relayout transpose).
    q: [B, 1, Hq, dh]."""
    B, _, Hq, dh = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(dh)
    qT = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qT = qT.reshape(B, Hkv, G, dh)
    s = jnp.einsum("bhgd,bhkd->bhgk", qT, k,
                   preferred_element_type=jnp.float32)
    kv_pos = jnp.arange(Tk)
    mask = kv_pos[None, :] < kv_valid_len[:, None] if kv_valid_len is not None \
        else jnp.ones((B, Tk), bool)
    mask &= kv_pos[None, :] <= q_positions[:, :1]
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bhkd->bhgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, 1, Hq, dh).astype(q.dtype)


def attention_core(q, k, v, *, q_positions, cfg: ModelConfig,
                   causal: bool = True, window: int = 0,
                   kv_valid_len=None) -> jnp.ndarray:
    """Dispatch between the Pallas kernel and the chunked-jnp path."""
    if cfg.use_kernels and q.shape[1] > 1:
        from repro.kernels import ops as kops
        return kops.flash_attention(
            q, k, v, q_positions=q_positions, causal=causal, window=window,
            kv_valid_len=kv_valid_len)
    if cfg.use_kernels and q.shape[1] == 1:
        from repro.kernels import ops as kops
        return kops.decode_attention(
            q, k, v, q_positions=q_positions, window=window,
            kv_valid_len=kv_valid_len)
    # decode (Tq == 1): single-block attention — scores are [B, Hq, 1, Tk]
    # (tiny), and the KV length stays a *contraction* dim that GSPMD shards
    # sequence-parallel instead of a scan axis it would have to replicate.
    chunk = k.shape[1] if q.shape[1] == 1 else cfg.attn_chunk
    return chunked_attention(
        q, k, v, q_positions=q_positions, causal=causal, window=window,
        kv_valid_len=kv_valid_len, chunk=chunk)


def reference_attention(q, k, v, *, q_positions, causal=True, window=0,
                        kv_valid_len=None, softmax_scale=None) -> jnp.ndarray:
    """Dense O(Tq·Tk) oracle (tests only)."""
    B, Tq, Hq, dh = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(dh)
    qg = q.reshape(B, Tq, Hkv, G, dh).astype(jnp.float32) * scale
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(jnp.float32))
    mask = _mask_for_chunk(q_positions, jnp.arange(Tk), causal=causal,
                           window=window, kv_valid_len=kv_valid_len, batch=B)
    s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(B, Tq, Hq, dh).astype(q.dtype)
