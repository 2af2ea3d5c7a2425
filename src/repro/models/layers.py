"""Shared neural-net layers: norms, positional embeddings, (quantizable)
linear projections, activations.

Everything is pure-functional: ``*_init(key, ...) -> params`` and
``*_apply(params, x, ...) -> y``.  Params are plain nested dicts of
``jnp.ndarray`` so the whole model is a pytree.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import hint

Params = Dict[str, jnp.ndarray]


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def trunc_normal(key, shape, scale: float, dtype) -> jnp.ndarray:
    """Truncated-normal init (±2σ) with fan-in scaling handled by caller."""
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Linear (optionally int4-quantized per paper §4.2)
# ---------------------------------------------------------------------------

def linear_init(key, in_dim: int, out_dim: int, cfg: ModelConfig,
                scale: Optional[float] = None) -> Params:
    scale = scale if scale is not None else 1.0 / math.sqrt(in_dim)
    return {"w": trunc_normal(key, (in_dim, out_dim), scale, _dtype(cfg))}


def linear_apply(params: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Dispatches dense vs int4-quantized weights.

    Quantized params carry ``w_int`` (int8 storage of int4 codes),
    ``scale`` [K/G, N] (power-of-2 when cfg.quant.pow2_scales — the BFP domain).
    """
    if "w_int" in params:
        from repro.kernels import ops as kops
        return kops.int4_matmul(x, params["w_int"], params["scale"],
                                use_kernel=cfg.use_kernels)
    return x @ params["w"]


def slice_linear(params: Params, lo: int, hi: int) -> Params:
    """Output-column slice of a (possibly quantized) linear param dict —
    the legacy split views over merged wqkv / w_gu weights.  Per-group
    scales index output columns, so slicing preserves the BFP grouping."""
    if "w_int" in params:
        return {"w_int": params["w_int"][..., lo:hi],
                "scale": params["scale"][..., lo:hi]}
    return {"w": params["w"][..., lo:hi]}


def fuse_norm_linear(cfg: ModelConfig) -> bool:
    """True when the fused norm-prologue linear pipeline dispatches: the
    Pallas path is on and the norm is RMS (the carried reduction is a
    single Σx²; layernorm's (μ, σ²) pair stays on the unfused path)."""
    return cfg.use_kernels and cfg.fuse_linear and cfg.norm_type == "rmsnorm"


def linear_fused(params: Params, x: jnp.ndarray, cfg: ModelConfig, *,
                 norm: Optional[Params] = None,
                 stats: Optional[jnp.ndarray] = None,
                 glu: bool = False, act: Optional[str] = None,
                 residual: Optional[jnp.ndarray] = None,
                 gate_mul: Optional[jnp.ndarray] = None,
                 emit_sq: bool = False):
    """One fused-pipeline matmul (norm-prologue × weight × epilogue).

    Callers pass the *un-normalized* activation plus the injected norm
    reduction (``stats`` == mean(x²)); the elementwise phase runs inside
    the kernel's k-loop.  Only dispatched when ``fuse_norm_linear(cfg)``
    (callers keep the composed norm_apply + linear_apply path otherwise)."""
    from repro.kernels import ops as kops
    return kops.fused_linear(
        params, x,
        mean_sq=None if norm is None else stats,
        gamma=None if norm is None else norm["gamma"],
        eps=cfg.norm_eps, glu=glu, act=act, residual=residual,
        gate_mul=gate_mul, emit_sq=emit_sq)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_init(dim: int, cfg: ModelConfig) -> Params:
    p = {"gamma": jnp.ones((dim,), _dtype(cfg))}
    if cfg.norm_type == "layernorm":
        p["beta"] = jnp.zeros((dim,), _dtype(cfg))
    return p


def norm_apply(params: Params, x: jnp.ndarray, cfg: ModelConfig,
               stats: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """RMSNorm / LayerNorm with fp32 statistics.

    ``stats`` lets the caller inject *precomputed* normalization statistics —
    the decoupled-reduction path of the paper's Alg. 1 (statistics are
    accumulated during the router matmul, elementwise phase runs later).
    For rmsnorm stats == mean(x²); for layernorm stats == (mean, var).
    """
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "rmsnorm":
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True) if stats is None \
            else stats[..., None]
        y = xf * jax.lax.rsqrt(ms + cfg.norm_eps)
        return (y * params["gamma"].astype(jnp.float32)).astype(x.dtype)
    if stats is None:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    else:
        mu, var = stats[0][..., None], stats[1][..., None]
    y = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
    y = y * params["gamma"].astype(jnp.float32) + params["beta"].astype(jnp.float32)
    return y.astype(x.dtype)


def norm_stats(x: jnp.ndarray, cfg: ModelConfig):
    """The reduction phase alone (paper Alg. 1 line 6).

    Layernorm variance uses the two-pass mean((x−μ)²) form — the one-pass
    E[x²]−μ² form cancels catastrophically for large-offset activations
    and diverged from ``norm_apply``'s own unfused computation."""
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "rmsnorm":
        return jnp.mean(xf * xf, axis=-1)
    mu = jnp.mean(xf, axis=-1)
    var = jnp.mean(jnp.square(xf - mu[..., None]), axis=-1)
    return (mu, var)


def rms_head_norm_init(dim: int, cfg: ModelConfig) -> Params:
    return {"gamma": jnp.ones((dim,), _dtype(cfg))}


def rms_head_norm(params: Params, x: jnp.ndarray, eps: float) -> jnp.ndarray:
    """Per-head qk-norm (RMS over head_dim)."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps)
            * params["gamma"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings (RoPE, partial RoPE, M-RoPE)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, rotary_pct: float, theta: float) -> jnp.ndarray:
    rot_dim = int(head_dim * rotary_pct) // 2 * 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32) / rot_dim))
    return inv  # [rot_dim // 2]


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, cfg: ModelConfig,
               ) -> jnp.ndarray:
    """x: [..., T, H, D]; positions: [B, T] (rope) or [3, B, T] (mrope)."""
    if cfg.pos_embedding not in ("rope", "mrope"):
        return x
    d = x.shape[-1]
    inv = rope_freqs(d, cfg.rotary_pct, cfg.rope_theta)      # [R/2]
    half = inv.shape[0]
    if cfg.pos_embedding == "mrope":
        # Sections (t, h, w) partition the R/2 frequency slots; each section
        # consumes its own position stream (Qwen2-VL M-RoPE).
        sec = cfg.mrope_sections
        assert sum(sec) == half, (sec, half)
        pos_f = positions.astype(jnp.float32)                # [3, B, T]
        freq_parts = []
        off = 0
        for s_i, n in enumerate(sec):
            freq_parts.append(pos_f[s_i][..., None] * inv[off:off + n])
            off += n
        freqs = jnp.concatenate(freq_parts, axis=-1)          # [B, T, R/2]
    else:
        freqs = positions.astype(jnp.float32)[..., None] * inv  # [B, T, R/2]
    cos = jnp.cos(freqs)[..., None, :]                        # [B, T, 1, R/2]
    sin = jnp.sin(freqs)[..., None, :]
    rot = 2 * half
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    x1, x2 = x_rot[..., :half], x_rot[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out1 = xf1 * cos - xf2 * sin
    out2 = xf2 * cos + xf1 * sin
    out = jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)
    if x_pass.shape[-1]:
        out = jnp.concatenate([out, x_pass], axis=-1)
    return out


def sinusoidal_positions(positions: jnp.ndarray, dim: int) -> jnp.ndarray:
    """[B, T] -> [B, T, dim] classic sinusoidal table (MusicGen-style)."""
    half = dim // 2
    freq = jnp.exp(-math.log(10_000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU / GELU)
# ---------------------------------------------------------------------------

def mlp_init(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    d_ff = d_ff or cfg.d_ff
    k1, k2 = jax.random.split(key, 2)
    glu = cfg.mlp_act in ("swiglu", "geglu")
    if glu:
        # widened [gate | up] projection: one matmul feeds the GLU epilogue
        p = {"gu": linear_init(k1, cfg.d_model, 2 * d_ff, cfg)}
    else:
        p = {"up": linear_init(k1, cfg.d_model, d_ff, cfg)}
    p["down"] = linear_init(k2, d_ff, cfg.d_model, cfg)
    return p


def mlp_act_name(cfg: ModelConfig) -> Optional[str]:
    return {"swiglu": "silu", "geglu": "gelu", "gelu_mlp": "gelu"}.get(
        cfg.mlp_act, "gelu")


def mlp_apply(params: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Dense MLP on an already-normalized activation (unfused path).
    Accepts both the merged ``gu`` layout and legacy split gate/up."""
    if "gu" in params:
        gu = linear_apply(params["gu"], x, cfg)
        F = gu.shape[-1] // 2
        g, up = gu[..., :F], gu[..., F:]
        h = (jax.nn.silu(g) if cfg.mlp_act == "swiglu"
             else jax.nn.gelu(g)) * up
    else:
        up = linear_apply(params["up"], x, cfg)
        if cfg.mlp_act == "swiglu":
            h = jax.nn.silu(linear_apply(params["gate"], x, cfg)) * up
        elif cfg.mlp_act == "geglu":
            h = jax.nn.gelu(linear_apply(params["gate"], x, cfg)) * up
        else:
            h = jax.nn.gelu(up)
    return linear_apply(params["down"], hint(h, "contracted"), cfg)


def mlp_apply_fused(params: Params, x: jnp.ndarray, cfg: ModelConfig, *,
                    norm: Params, stats: jnp.ndarray,
                    residual: Optional[jnp.ndarray] = None,
                    gate_mul: Optional[jnp.ndarray] = None,
                    emit_sq: bool = False):
    """Fused-pipeline dense MLP on the *un-normalized* activation:
    norm-prologue × widened [gate|up] × GLU epilogue, then the down
    projection with the gate-multiplier/residual/Σy² epilogue.  The
    normalized activation and the GLU intermediate never round-trip HBM
    separately from their matmuls.  Returns (y_or_residual_out, Σy²|None).
    """
    glu = "gu" in params
    h, _ = linear_fused(params["gu"] if glu else params["up"], x, cfg,
                        norm=norm, stats=stats, glu=glu,
                        act=mlp_act_name(cfg))
    return linear_fused(params["down"], h, cfg, residual=residual,
                        gate_mul=gate_mul, emit_sq=emit_sq)


def mlp_fusable(params: Params) -> bool:
    """Dense-MLP param dicts the fused pipeline understands: merged
    [gate|up] or plain up/down.  MoE keeps its scatter-dispatch path and
    legacy *split* GLU params fall back to the composed ops (run them
    through ``merge_legacy_linear_params`` to enable fusion)."""
    return "gu" in params or ("up" in params and "down" in params
                              and "gate" not in params)


def _concat_linears(parts) -> Params:
    """Column-concat linear param dicts.  All-quantized parts concat in
    the code domain; a mixed dense/int4 list (quantize_params' size
    threshold can split a legacy wq/wk/wv trio) is dequantized to a dense
    merge — correctness over storage for that corner.  Leaves may carry
    a leading scan-stacked stage axis: everything indexes from the end."""
    if all("w_int" in p for p in parts) and len(
            {p["w_int"].shape[-2] for p in parts}) == 1:
        return {"w_int": jnp.concatenate([p["w_int"] for p in parts], -1),
                "scale": jnp.concatenate([p["scale"] for p in parts], -1)}
    from repro.quant import dequantize

    dense = [p for p in parts if "w" in p]
    k = dense[0]["w"].shape[-2] if dense else parts[0]["w_int"].shape[-2]
    dt = dense[0]["w"].dtype if dense else jnp.float32
    ws = [p["w"] if "w" in p
          else dequantize(p["w_int"], p["scale"], k=k).astype(dt)
          for p in parts]
    return {"w": jnp.concatenate(ws, axis=-1)}


def merge_legacy_linear_params(params: Params) -> Params:
    """Weight-merge shim: convert legacy split projections — attention
    {wq, wk, wv} and GLU-MLP {gate, up} — into the merged ``wqkv`` /
    ``gu`` layouts the fused pipeline uses.  Works on dense and
    int4-quantized trees (checkpoints from either era load fine)."""
    def walk(tree):
        if not isinstance(tree, dict):
            return tree
        out = {k: walk(v) for k, v in tree.items()}
        if {"wq", "wk", "wv"} <= set(out):
            out["wqkv"] = _concat_linears(
                [out.pop("wq"), out.pop("wk"), out.pop("wv")])
        if {"gate", "up", "down"} <= set(out) and isinstance(
                out["gate"], dict) and ("w" in out["gate"]
                                        or "w_int" in out["gate"]):
            out["gu"] = _concat_linears([out.pop("gate"), out.pop("up")])
        return out

    return walk(params)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def embedding_init(key, cfg: ModelConfig) -> Params:
    p = {"table": trunc_normal(key, (cfg.vocab_size, cfg.d_model), 0.02, _dtype(cfg))}
    return p


def embed(params: Params, tokens: jnp.ndarray) -> jnp.ndarray:
    return params["table"][tokens]


def unembed(params: Params, head_params: Optional[Params], x: jnp.ndarray,
            cfg: ModelConfig) -> jnp.ndarray:
    if cfg.tie_embeddings:
        return x @ params["table"].T
    return linear_apply(head_params, x, cfg)
