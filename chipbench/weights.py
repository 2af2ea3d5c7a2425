"""The served weights, drawn on the device from the seed.

One recipe serves the program and the reference.  Each layer's key comes
from the seed's key; a layer draws, from truncated normals cast to
bfloat16, its [q|k|v] projection (scale 1/sqrt(d)), o projection
(1/sqrt(heads x head_dim)), [gate|up] projection (1/sqrt(d)) and down
projection (1/sqrt(d_ff)), plus one router per block (``router``).
A router decides per token: its keep-minus-skip logit is a bias of +1
(75% of the blocks, at layers the seed draws: ``keep_masks``) or -1,
plus a random linear map of the residual whose spread over tokens is
about ``ROUTER_SPREAD``.  So most tokens follow the bias and some go the
other way, and few logits lie within rounding of the threshold.
The embedding and the output head draw at 0.02.  Every matrix of at
least 2**16 elements is int4-coded: round to nearest per group of 128
input rows, with power-of-two scales.  Norm gains are 1.

``program_params`` lays the weights out as the engine reads them (a
first layer, then the other layers stacked) in one jitted call, layer
by layer inside it, so no float32 copy of the model is ever whole; the
output head is coded a block of columns at a time for the same reason.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

GROUP = 128
KEEP_SHARE = 0.75       # blocks a router keeps (SkipGPT prunes 25%)
ROUTER_MARGIN = 1.0     # |bias| of a router's keep-minus-skip logit
ROUTER_SPREAD = 0.4     # scale of the residual's part of a router logit
# Mean square of the residual entering layer l's routers, as these
# random weights make it (about EMBED_MS + RESIDUAL_GROWTH * l at both
# configurations' widths; measured on the float32 reference).  It sets
# each router weight's scale, so the spread is about the same at every
# depth.
EMBED_MS = (0.02 * 0.88) ** 2
RESIDUAL_GROWTH = 0.42
MIN_CODED = 1 << 16
HEAD_BLOCK = 16384      # output-head columns coded at a time


class Dims(NamedTuple):
    layers: int
    d: int
    hq: int
    hkv: int
    dh: int
    ff: int
    vocab: int
    eps: float
    theta: float
    qk_norm: bool


def dims_of(conf: dict) -> Dims:
    """The shape of a configuration file, read from its published keys."""
    d, hq = conf["hidden_size"], conf["num_attention_heads"]
    return Dims(conf["num_hidden_layers"], d, hq, conf["num_key_value_heads"],
                conf.get("head_dim", d // hq), conf["intermediate_size"],
                conf["vocab_size"], float(conf["rms_norm_eps"]),
                float(conf["rope_theta"]), bool(conf.get("qk_norm", False)))


def trunc(key, shape, scale):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(jnp.bfloat16)


def coded(w_bf16):
    """int4 codes (int8 storage, [ceil(K/G)*G, N]) and float32 scales
    ([ceil(K/G), N]) of one matrix."""
    w = w_bf16.astype(jnp.float32)
    K, N = w.shape
    G = min(GROUP, K)
    Kp = -(-K // G) * G
    w = jnp.pad(w, ((0, Kp - K), (0, 0))).reshape(Kp // G, G, N)
    amax = jnp.abs(w).max(axis=1)
    scale = jnp.exp2(jnp.ceil(jnp.log2(jnp.maximum(amax / 7, 1e-12))))
    scale = jnp.where(amax == 0, 1.0, scale)
    codes = jnp.clip(jnp.round(w / scale[:, None, :]), -8, 7)
    return codes.reshape(Kp, N).astype(jnp.int8), scale


def dequantized(w_bf16):
    """The matrix as served, in float32: int4-coded when large enough."""
    K, N = w_bf16.shape
    if K * N < MIN_CODED:
        return w_bf16.astype(jnp.float32)
    codes, scale = coded(w_bf16)
    G = codes.shape[0] // scale.shape[0]
    w = codes.astype(jnp.float32).reshape(scale.shape[0], G, N)
    return (w * scale[:, None, :]).reshape(-1, N)[:K]


def layer_keys(key, layers: int):
    ks = jax.random.split(key, 4)
    sk = jax.random.split(ks[1], layers)
    return jax.vmap(lambda k: jax.random.split(k, 1)[0])(sk)


def keep_masks(key, layers: int):
    """Which blocks' routers lean to keeping, [layers] bool each for
    attention and MLP: a share KEEP_SHARE of them, at layers drawn from
    the seed; the others lean to skipping.  Attention at layer 0 always
    leans to keeping (it computes every token's K/V anyway).  The counts
    are fixed, so the seeds differ in work only by the tokens that go
    against their router's lean."""
    ka, km = jax.random.split(jax.random.fold_in(key, 1))
    n_attn = round(KEEP_SHARE * (layers - 1))
    attn = jnp.zeros((layers,), bool).at[0].set(True).at[
        1 + jax.random.permutation(ka, layers - 1)[:n_attn]].set(True)
    mlp = jnp.zeros((layers,), bool).at[
        jax.random.permutation(km, layers)[:round(KEEP_SHARE * layers)]
    ].set(True)
    return attn, mlp


def router(key, d: int, depth, keep):
    """One block's router: logits [skip, keep] = x @ w + b, with
    w[:, 0] = 0, b = [0, +ROUTER_MARGIN] where ``keep`` else
    [0, -ROUTER_MARGIN], and w[:, 1] random at a scale that gives
    x @ w[:, 1] a spread of about ROUTER_SPREAD over tokens for a
    residual whose mean square is EMBED_MS + RESIDUAL_GROWTH * depth."""
    rms = jnp.sqrt(EMBED_MS + RESIDUAL_GROWTH * depth)
    col = jax.random.truncated_normal(key, -2.0, 2.0, (d,), jnp.float32)
    col = col * (ROUTER_SPREAD / (0.88 * math.sqrt(d) * rms))
    return {"w": jnp.stack([jnp.zeros_like(col), col], axis=1),
            "b": jnp.stack([jnp.float32(0.0),
                            jnp.where(keep, ROUTER_MARGIN, -ROUTER_MARGIN)
                            .astype(jnp.float32)])}


def layer_draws(key, dm: Dims, layer, keep_attn, keep_mlp) -> Dict:
    """Layer ``layer``'s routers (float32) and matrices (bfloat16, as
    drawn)."""
    ai, ki = dm.hq * dm.dh, dm.hkv * dm.dh
    b = jax.random.split(key, 4)
    a = jax.random.split(b[1], 2)
    m = jax.random.split(b[3], 2)
    r = jax.random.split(b[2], 2)
    return {
        "r_attn": router(r[0], dm.d, layer, keep_attn),
        "r_mlp": router(r[1], dm.d, layer + 0.02, keep_mlp),
        "wqkv": trunc(a[0], (dm.d, ai + 2 * ki), 1 / math.sqrt(dm.d)),
        "wo": trunc(a[1], (ai, dm.d), 1 / math.sqrt(ai)),
        "gu": trunc(m[0], (dm.d, 2 * dm.ff), 1 / math.sqrt(dm.d)),
        "down": trunc(m[1], (dm.ff, dm.d), 1 / math.sqrt(dm.ff)),
    }


def _linear(w):
    K, N = w.shape
    if K * N < MIN_CODED:
        return {"w": w}
    codes, scale = coded(w)
    return {"w_int": codes, "scale": scale}


def _block(key, layer, keep_attn, keep_mlp, dm: Dims):
    dr = layer_draws(key, dm, layer, keep_attn, keep_mlp)
    gain = lambda n: {"gamma": jnp.ones((n,), jnp.bfloat16)}  # noqa: E731
    inner = {"wqkv": _linear(dr["wqkv"]), "wo": _linear(dr["wo"])}
    if dm.qk_norm:
        inner["qnorm"], inner["knorm"] = gain(dm.dh), gain(dm.dh)
    return {"pos0": {
        "mixer": {"router": dr["r_attn"], "norm": gain(dm.d),
                  "inner": inner},
        "ffn": {"router": dr["r_mlp"], "norm": gain(dm.d),
                "inner": {"gu": _linear(dr["gu"]),
                          "down": _linear(dr["down"])}}}}


def head_draw(key, dm: Dims):
    return trunc(jax.random.split(key, 4)[2], (dm.d, dm.vocab), 0.02)


def embed_draw(key, dm: Dims):
    return trunc(jax.random.split(key, 4)[0], (dm.vocab, dm.d), 0.02)


def _head(key, dm: Dims):
    w = head_draw(key, dm)
    n = next((n for n in range(1, dm.vocab + 1) if dm.vocab % n == 0
              and dm.vocab // n <= HEAD_BLOCK), 1)
    blocks = w.reshape(dm.d, n, dm.vocab // n).transpose(1, 0, 2)
    codes, scale = jax.lax.map(coded, blocks)
    return {"w_int": codes.transpose(1, 0, 2).reshape(codes.shape[1], -1),
            "scale": scale.transpose(1, 0, 2).reshape(scale.shape[1], -1)}


@functools.partial(jax.jit, static_argnums=1)
def program_params(key, dm: Dims):
    """The engine's parameter tree (``repro.models.model`` layout)."""
    lk = layer_keys(key, dm.layers)
    ka, km = keep_masks(key, dm.layers)
    head = _head(key, dm)
    depth = jnp.arange(dm.layers, dtype=jnp.float32)
    stack = {"stage0": _block(lk[0], depth[0], ka[0], km[0], dm)}
    if dm.layers > 1:
        stack["stages"] = jax.lax.map(
            lambda xs: _block(*xs, dm), (lk[1:], depth[1:], ka[1:], km[1:]))
    return {"embed": {"table": embed_draw(key, dm)}, "stack": stack,
            "final_norm": {"gamma": jnp.ones((dm.d,), jnp.bfloat16)},
            "lm_head": head}
