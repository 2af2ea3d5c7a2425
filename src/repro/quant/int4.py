"""GPTQ-style symmetric INT4 weight quantization (paper §5.1) with
power-of-2 ("BFP-friendly") per-group scales (paper §4.2.2).

Round-to-nearest per group of ``group_size`` input-channel rows.  Power-of-2
scales put the dequantization into a shared-exponent domain so the matmul
kernel can accumulate int8×int4 products in *fixed point* and reconstruct
floating point once per group — the TPU analogue of the paper's BFP
accumulation tree.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, jnp.ndarray]

INT4_MIN, INT4_MAX = -8, 7
# Smallest weight (elements of one layer's matrix) quantize_params codes;
# routers and norms are far below it and stay in floating point.
MIN_SIZE = 1 << 16


def quantize_rtn(w: jnp.ndarray, group_size: int = 128,
                 pow2_scales: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """w: [K, N] -> (codes int8 in [-8, 7] of shape [ceil(K/G)·G, N],
    scales fp32 [ceil(K/G), N]).

    When K is not a group multiple the final group is zero-padded: the
    padding rows can never raise a group's amax (masked-amax equivalent —
    |0| <= any real amax) and the zero codes contribute nothing to the
    accumulation, so matmuls just zero-pad the activation's K to match
    (``kernels/ops.int4_matmul`` / ``fused_linear`` do this)."""
    K, N = w.shape
    G = min(group_size, K)
    Kp = -(-K // G) * G
    wf = w.astype(jnp.float32)
    if Kp != K:
        wf = jnp.pad(wf, ((0, Kp - K), (0, 0)))
    wg = wf.reshape(Kp // G, G, N)
    amax = jnp.abs(wg).max(axis=1)                       # [K/G, N]
    scale = amax / INT4_MAX
    if pow2_scales:
        # smallest power of 2 >= scale (exact BFP exponent domain)
        scale = jnp.exp2(jnp.ceil(jnp.log2(jnp.maximum(scale, 1e-12))))
    scale = jnp.where(amax == 0, 1.0, scale)
    codes = jnp.clip(jnp.round(wg / scale[:, None, :]), INT4_MIN, INT4_MAX)
    return codes.reshape(Kp, N).astype(jnp.int8), scale


def dequantize(codes: jnp.ndarray, scale: jnp.ndarray,
               k: int = 0) -> jnp.ndarray:
    """codes: [..., Kw, N] (possibly group-padded; leading stage axes
    allowed) -> [..., k or Kw, N] fp32."""
    *lead, Kw, N = codes.shape
    G = Kw // scale.shape[-2]
    wg = (codes.astype(jnp.float32).reshape(*lead, Kw // G, G, N)
          * scale[..., :, None, :])
    w = wg.reshape(*lead, Kw, N)
    return w[..., :k, :] if k else w


def quantize_params(params: Params, group_size: int = 128,
                    pow2_scales: bool = True,
                    min_size: int = MIN_SIZE) -> Params:
    """Replace every linear weight leaf named ``w`` with {w_int, scale}
    (large matrices only — routers/norms stay fp).

    2-D leaves are one layer's [K, N]; the scan-stacked layers under
    ``stages`` are [S, K, N] and are quantized per stage (the size
    threshold applies to one stage's matrix), so the codes and scales
    keep the leading stage axis the scan slices.  Weights whose input
    dim is not a group multiple are group-padded by ``quantize_rtn``
    (the matmul wrappers zero-pad the activation), so no eligible weight
    is silently skipped."""
    q = functools.partial(quantize_rtn, group_size=group_size,
                          pow2_scales=pow2_scales)

    def walk(tree, stacked):
        if isinstance(tree, dict):
            out = {}
            for k, v in tree.items():
                if (k == "w" and hasattr(v, "ndim")
                        and v.ndim == (3 if stacked else 2)
                        and v.size // (v.shape[0] if stacked else 1)
                        >= min_size):
                    out["w_int"], out["scale"] = (jax.vmap(q)(v) if stacked
                                                  else q(v))
                else:
                    out[k] = walk(v, stacked or k == "stages")
            return out
        return tree

    return walk(params, False)
