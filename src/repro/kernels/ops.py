"""Public jit'd wrappers around the Pallas kernels.

Handles layout packing (GQA head packing), padding, backend dispatch
(interpret=True on the CPU backend only, so CPU tests execute the kernel
bodies; on any other backend a kernel compiles for real or raises), and
the pure-jnp fallbacks used by the dry-run lowering.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_packed
from repro.kernels.fused_linear import fused_linear_pallas
from repro.kernels.fused_router_rmsnorm import router_stats_pallas
from repro.kernels.int4_matmul import int4_matmul_pallas
from repro.kernels.paged_attention import paged_attention_packed


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def _pack_heads(q, k, v, q_positions, kv_valid_len):
    B, Tq, Hq, dh = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    # q rows pack (G, Tq): every KV tile is reused by all G grouped q-heads.
    qp = (q.reshape(B, Tq, Hkv, G, dh)
          .transpose(0, 2, 3, 1, 4)
          .reshape(B * Hkv, G * Tq, dh))
    kp = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Tk, dh)
    vp = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Tk, dh)
    pos = jnp.broadcast_to(q_positions[:, None, None, :],
                           (B, Hkv, G, Tq)).reshape(B * Hkv, G * Tq)
    if kv_valid_len is None:
        kv_len = jnp.full((B * Hkv, 1), Tk, jnp.int32)
    else:
        kv_len = jnp.broadcast_to(kv_valid_len[:, None, None],
                                  (B, Hkv, 1)).reshape(B * Hkv, 1)
    return qp, kp, vp, pos, kv_len, (B, Tq, Hq, Hkv, G, dh)


def flash_attention(q, k, v, *, q_positions, causal: bool = True,
                    window: int = 0, kv_valid_len=None,
                    softmax_scale: Optional[float] = None) -> jnp.ndarray:
    """q: [B,Tq,Hq,dh]; k/v: [B,Tk,Hkv,dh] -> [B,Tq,Hq,dh]."""
    scale = softmax_scale if softmax_scale is not None \
        else 1.0 / math.sqrt(q.shape[-1])
    qp, kp, vp, pos, kv_len, meta = _pack_heads(
        q, k, v, q_positions, kv_valid_len)
    B, Tq, Hq, Hkv, G, dh = meta
    out = flash_attention_packed(qp, kp, vp, pos, kv_len, causal=causal,
                                 window=window, scale=scale,
                                 interpret=_interpret())
    return (out.reshape(B, Hkv, G, Tq, dh)
            .transpose(0, 3, 1, 2, 4)
            .reshape(B, Tq, Hq, dh))


def decode_attention(q, k, v, *, q_positions, window: int = 0,
                     kv_valid_len=None,
                     softmax_scale: Optional[float] = None) -> jnp.ndarray:
    """Single-token decode: q [B,1,Hq,dh] against a [B,Tk,Hkv,dh] cache.
    The packed layout makes this flash-decoding: the G grouped q-heads are
    the rows, the KV length is the reduction."""
    return flash_attention(q, k, v, q_positions=q_positions, causal=True,
                           window=window, kv_valid_len=kv_valid_len,
                           softmax_scale=softmax_scale)


def paged_decode_attention(q, k_pages, v_pages, block_table, eff_pos,
                           k_tok, v_tok, *, q_positions,
                           softmax_scale: Optional[float] = None,
                           k_scales=None, v_scales=None, kv_dtype=None
                           ) -> jnp.ndarray:
    """Single-token decode against the paged KV store.

    The kernel walks each slot's block table (physical pages resolved via
    scalar prefetch) with history-buffer masking by effective position and
    returns raw online-softmax state; the in-flight token's KV — committed
    to the store only at end-of-step — is folded in here with one more
    online-softmax update.

    q: [B, 1, Hq, dh]; k/v pages: [P, ps, Hkv, dh] (int8 codes when
    ``kv_dtype`` is set, with ``k_scales``/``v_scales`` [P, ps, Hkv]);
    block_table: [B, J]; eff_pos: [B, J·ps]; k_tok/v_tok: [B, 1, Hkv, dh]
    (always full precision — in-flight KV is quantized only at commit);
    q_positions: [B, 1].
    """
    B, _, Hq, dh = q.shape
    P, ps, Hkv, _ = k_pages.shape
    J = block_table.shape[1]
    G = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None \
        else 1.0 / math.sqrt(dh)

    qp = (q.reshape(B, 1, Hkv, G, dh)
          .transpose(0, 2, 3, 1, 4)
          .reshape(B * Hkv, G, dh))
    pos = jnp.broadcast_to(q_positions[:, None, :],
                           (B, Hkv, G)).reshape(B * Hkv, G)
    acc, m, l = paged_attention_packed(
        qp, k_pages, v_pages, block_table.astype(jnp.int32),
        eff_pos.reshape(B, J, ps), pos.astype(jnp.int32),
        scale=scale, interpret=_interpret(),
        k_scales=k_scales, v_scales=v_scales, kv_dtype=kv_dtype)

    # fold in the current token (always causally valid: key pos == q pos)
    kt = k_tok.reshape(B, Hkv, dh)
    kt = jnp.broadcast_to(kt[:, :, None], (B, Hkv, G, dh)).reshape(
        B * Hkv, G, dh)
    vt = v_tok.astype(jnp.float32).reshape(B, Hkv, dh)
    vt = jnp.broadcast_to(vt[:, :, None], (B, Hkv, G, dh)).reshape(
        B * Hkv, G, dh)
    s_tok = jnp.einsum("bgd,bgd->bg", qp.astype(jnp.float32) * scale,
                       kt.astype(jnp.float32))
    m2 = jnp.maximum(m, s_tok)
    alpha = jnp.exp(m - m2)
    p_tok = jnp.exp(s_tok - m2)
    l2 = l * alpha + p_tok
    out = (acc * alpha[..., None] + p_tok[..., None] * vt) \
        / jnp.maximum(l2, 1e-20)[..., None]
    return (out.reshape(B, Hkv, G, dh)
            .reshape(B, 1, Hq, dh).astype(q.dtype))


# ---------------------------------------------------------------------------
# int4 matmul (BFP accumulation)
# ---------------------------------------------------------------------------

def int4_matmul(x: jnp.ndarray, w_codes: jnp.ndarray, scale: jnp.ndarray,
                use_kernel: bool = False) -> jnp.ndarray:
    """x: [..., K] × int4-coded [Kw, N] -> [..., N].

    ``Kw >= K`` covers group-padded quantized weights (quantize_rtn pads
    the final group with zero codes when K is not a group multiple); the
    activation is zero-padded to match."""
    lead = x.shape[:-1]
    K = x.shape[-1]
    Kw, N = w_codes.shape
    x2 = x.reshape(-1, K)
    if Kw != K:
        x2 = jnp.pad(x2, ((0, 0), (0, Kw - K)))
    if use_kernel:
        out = int4_matmul_pallas(x2, w_codes, scale, interpret=_interpret())
    else:
        # jnp fallback: dequantize-and-matmul; XLA keeps the int8 weight
        # feed (weight HBM bytes = 1/2 of bf16; accounted at 4-bit in the
        # roofline, DESIGN.md).
        G = Kw // scale.shape[0]
        w = (w_codes.astype(x.dtype).reshape(Kw // G, G, N)
             * scale[:, None, :].astype(x.dtype)).reshape(Kw, N)
        out = x2 @ w
    return out.reshape(*lead, N)


# ---------------------------------------------------------------------------
# Fused router + RMSNorm statistics
# ---------------------------------------------------------------------------

def ssd_scan(xh, dt, A_log, Bm, Cm, chunk: int) -> jnp.ndarray:
    """Mamba-2 SSD chunk scan (state carried in VMEM across chunks)."""
    from repro.kernels.ssd_scan import ssd_scan_pallas
    return ssd_scan_pallas(xh, dt, A_log, Bm, Cm, chunk,
                           interpret=_interpret())


def fused_router_rmsnorm_stats(x: jnp.ndarray, w: jnp.ndarray,
                               b: jnp.ndarray):
    """x: [B, T, D] -> (router logits [B, T, 2] f32, mean_sq [B, T] f32)."""
    B, T, D = x.shape
    logits, ms = router_stats_pallas(x.reshape(B * T, D), w,
                                     interpret=_interpret())
    return logits.reshape(B, T, 2) + b, ms.reshape(B, T)


def fused_linear(params, x: jnp.ndarray, *, mean_sq=None, gamma=None,
                 eps: float = 1e-5, glu: bool = False, act=None,
                 residual=None, gate_mul=None, emit_sq: bool = False,
                 use_kernel: bool = True):
    """Fused linear pipeline over a (possibly quantized) linear param dict.

    x: [..., K]; params: {"w"} (dense) or {"w_int", "scale"} (int4-BFP).
    ``mean_sq`` [...] + ``gamma`` [K] fuse the RMSNorm elementwise phase
    into the k-loop (Alg. 1 ll. 11–15); ``glu``/``act`` apply the
    SwiGLU/GeGLU epilogue over a widened [gate|up] weight; ``gate_mul``
    [...] and ``residual`` [..., F] fuse the routed-residual write; with
    ``emit_sq`` the second return is Σy² per row (f32) — the next block's
    norm reduction (incremental-reduction carry).  Returns (out, sq|None).
    """
    lead = x.shape[:-1]
    K = x.shape[-1]
    x2 = x.reshape(-1, K)
    kw = dict(
        mean_sq=None if mean_sq is None else mean_sq.reshape(-1),
        gamma=gamma, eps=eps, glu=glu, act=act,
        residual=None if residual is None
        else residual.reshape(-1, residual.shape[-1]),
        gate_mul=None if gate_mul is None else gate_mul.reshape(-1),
        emit_sq=emit_sq)
    if "w_int" in params:
        args = dict(w_codes=params["w_int"], scale=params["scale"])
    else:
        args = dict(w=params["w"])
    if use_kernel:
        out, sq = fused_linear_pallas(x2, **args, **kw,
                                      interpret=_interpret())
    else:
        out, sq = ref.fused_linear_ref(x2, **args, **kw)
    F = out.shape[-1]
    out = out.reshape(*lead, F)
    return out, (None if sq is None else sq.reshape(*lead))
