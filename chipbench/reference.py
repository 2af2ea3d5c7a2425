"""Plain float32 reference of the routed dense GQA model the engine
serves, independent of the program (it imports nothing of ``repro``).

The architecture: pre-norm decoder layers, each an attention block and
a SwiGLU MLP block; every block has a SkipGPT router (a linear map of
the raw residual to two logits, keep = logit[1] > logit[0], decided per
token) whose 0/1 gate multiplies the block's output.  Attention is grouped-query with
per-head RMS qk-norm (where the configuration has it) and rotary
embeddings (rotate-half, theta from the configuration).  Cross-layer KV
reuse: a token whose attention gate is closed at layer l is attended
with the K/V of the last layer at which it executed; layer 0 always
computes K/V.  The weights are the seeded random int4 weights the
benchmark serves (``weights.py``), drawn here from the seed and used
dequantized, which is exact.  Everything else is float32 under
``jax.default_matmul_precision("highest")``.

Departures from the published models (also listed in PERF.md): no RoPE
scaling (DeepSeek-Coder-33B declares a linear factor of 4 for its 16k
context; the cells stay below its original 4k); random weights; the
routers are SkipGPT's, which the published models do not have.

``lowp=True`` is the control: the same computation one precision step
below what the configuration states.  The program feeds its int4
matmuls 8-bit block floating point activations (the paper's BFP path),
so the control feeds them 4-bit block floating point; attention runs on
bfloat16 q, k and v, so the control rounds those to float8 e4m3.  The
model runs layer by layer over a few sequences, so any depth fits on the
chip once the program's state is freed.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights

GROUP = 128             # BFP group of the control's matmul inputs
T_BUCKET = 512          # sequences are right-padded to a multiple of this

Dims = weights.Dims
dims_of = weights.dims_of


@functools.partial(jax.jit, static_argnums=1)
def layer_weights(key, dm: Dims, layer, keep_attn, keep_mlp):
    """One layer's weights as served, in float32."""
    dr = weights.layer_draws(key, dm, layer, keep_attn, keep_mlp)
    return {k: (v if k.startswith("r_") else weights.dequantized(v))
            for k, v in dr.items()}


@functools.partial(jax.jit, static_argnums=1)
def embed_table(key, dm: Dims):
    return weights.embed_draw(key, dm)


@functools.partial(jax.jit, static_argnums=1)
def head_weight(key, dm: Dims):
    return weights.dequantized(weights.head_draw(key, dm))


def _f8(x, lowp):
    """An attention input as the control holds it: float8 e4m3."""
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32) if lowp else x


def _bfp4(x, lowp):
    """A matmul's activation input as the control holds it: block floating
    point with a 4-bit mantissa, one power-of-two exponent per row and
    group of 128 (the program's BFP path holds 8 bits)."""
    if not lowp:
        return x
    shape = x.shape
    g = x.reshape(shape[:-1] + (shape[-1] // GROUP, GROUP))
    amax = jnp.abs(g).max(axis=-1, keepdims=True)
    pe = jnp.exp2(jnp.ceil(jnp.log2(jnp.maximum(amax, 1e-30))))
    mant = jnp.clip(jnp.round(g * 8 / pe), -8, 7)
    return (mant * pe / 8).reshape(shape)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    f = pos.astype(jnp.float32)[:, None] * inv              # [T, d/2]
    cos, sin = jnp.cos(f)[:, None, :], jnp.sin(f)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _gate(r, x):
    lg = x @ r["w"] + r["b"]
    return (lg[..., 1] > lg[..., 0]).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def layer_apply(w, x, vk, vv, n_real, dm: Dims, first: bool, lowp: bool):
    """One layer over one right-padded sequence.  x: [T, D]; vk/vv: the
    K/V view [T, Hkv, dh] of the previous layer (ignored at layer 0).
    Returns (x, vk, vv, attention gates kept, MLP gates kept) with the
    gate counts over the first ``n_real`` positions."""
    T = x.shape[0]
    G = dm.hq // dm.hkv
    ai, ki = dm.hq * dm.dh, dm.hkv * dm.dh
    pos = jnp.arange(T)
    real = (pos < n_real).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        ga = _gate(w["r_attn"], x)
        h = _bfp4(_rms(x, dm.eps), lowp)
        qkv = h @ w["wqkv"]
        q = qkv[:, :ai].reshape(T, dm.hq, dm.dh)
        k = qkv[:, ai:ai + ki].reshape(T, dm.hkv, dm.dh)
        v = qkv[:, ai + ki:].reshape(T, dm.hkv, dm.dh)
        if dm.qk_norm:
            q, k = _rms(q, dm.eps), _rms(k, dm.eps)
        q, k = _rope(q, pos, dm.theta), _rope(k, pos, dm.theta)
        if not first:
            keep = ga[:, None, None] > 0
            k, v = jnp.where(keep, k, vk), jnp.where(keep, v, vv)
        qs = _f8(q, lowp).reshape(T, dm.hkv, G, dm.dh) / math.sqrt(dm.dh)
        kk, vvv = _f8(k, lowp), _f8(v, lowp)
        s = jnp.einsum("qhgd,khd->hgqk", qs, kk)
        s = jnp.where(pos[None, None, None, :] <= pos[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hgqk,khd->qhgd", p, vvv).reshape(T, ai)
        x = x + ga[:, None] * (_bfp4(o, lowp) @ w["wo"])
        gm = _gate(w["r_mlp"], x)
        h = _bfp4(_rms(x, dm.eps), lowp)
        gu = h @ w["gu"]
        y = _bfp4(jax.nn.silu(gu[:, :dm.ff]) * gu[:, dm.ff:], lowp) @ w["down"]
        x = x + gm[:, None] * y
    return x, k, v, (ga * real).sum(), (gm * real).sum()


@functools.partial(jax.jit, static_argnums=(3, 4))
def head_apply(wlm, x, rows, dm: Dims, lowp: bool):
    """Logits [R, V] at the given positions of the final residual."""
    with jax.default_matmul_precision("highest"):
        return _bfp4(_rms(x[rows], dm.eps), lowp) @ wlm


class Result(NamedTuple):
    logits: List[jnp.ndarray]       # per sequence, [rows, V] on device
    attn_keep: float                # share of (token, layer) attention kept
    mlp_keep: float
    entries: List[int]              # per sequence: KV entries stored
    against: float                  # share of gates against their lean


def run(conf: dict, key, seqs: Sequence[np.ndarray],
        rows: Sequence[np.ndarray], lowp_modes=(False,)
        ) -> Dict[bool, Result]:
    """Logits of each sequence at its ``rows`` (next-token logits after
    those positions), for each precision in ``lowp_modes``."""
    dm = dims_of(conf)
    table = embed_table(key, dm)
    state = {}
    for lowp in lowp_modes:
        state[lowp] = []
        for s in seqs:
            T = -(-len(s) // T_BUCKET) * T_BUCKET
            tok = np.zeros((T,), np.int32)
            tok[:len(s)] = s
            x = table[jnp.asarray(tok)].astype(jnp.float32)
            z = jnp.zeros((T, dm.hkv, dm.dh), jnp.float32)
            state[lowp].append([x, z, z])
    del table
    L = dm.layers
    kept = {lowp: np.zeros((len(seqs), L, 2)) for lowp in lowp_modes}
    ka, km = weights.keep_masks(key, L)
    for li, lk in enumerate(weights.layer_keys(key, L)):
        w = layer_weights(lk, dm, jnp.float32(li), ka[li], km[li])
        for lowp in lowp_modes:
            for i, s in enumerate(seqs):
                x, vk, vv = state[lowp][i]
                x, vk, vv, ga, gm = layer_apply(
                    w, x, vk, vv, jnp.int32(len(s)), dm, li == 0, lowp)
                state[lowp][i] = [x, vk, vv]
                kept[lowp][i, li] = (ga, gm)
        del w
    wlm = head_weight(key, dm)
    n = np.array([len(s) for s in seqs], np.float64)
    lean = np.stack([np.asarray(ka), np.asarray(km)], axis=1)   # [L, 2]
    out = {}
    for lowp in lowp_modes:
        logits = [head_apply(wlm, st[0], jnp.asarray(r, jnp.int32), dm, lowp)
                  for st, r in zip(state[lowp], rows)]
        k = kept[lowp]                                  # [S, L, 2] counts
        against = np.where(lean, n[:, None, None] - k, k).sum()
        out[lowp] = Result(
            logits, float(k[..., 0].sum() / (n.sum() * L)),
            float(k[..., 1].sum() / (n.sum() * L)),
            # layer 0 stores every token's entry; a later layer, the
            # tokens whose attention gate is open
            [int(round(v)) for v in n + k[:, 1:, 0].sum(axis=1)],
            float(against / (n.sum() * L * 2)))
    return out
