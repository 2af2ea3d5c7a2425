"""Paged decode-attention Pallas kernel (TPU target; interpret=True on CPU).

Decode attention against the paged KV store (``repro/kvcache/paged.py``):
the grid's inner dimension walks one slot's *block table* — each step's KV
tile is a physical page, resolved through the scalar-prefetched table in
the BlockSpec index_map (the history-buffer indirection: the same physical
page can appear in several layers' walks).  Masking is by *effective
position* (``repro/kvcache/history.py``): entries invalid at the querying
layer carry a sentinel position the causal test can never admit, so the
pruned-token history is skipped without any per-entry gather.

Online-softmax machinery (running max ``m``, running Σexp ``l`` in VMEM
scratch) is the same dataflow as ``kernels/flash_attention.py``; this
kernel returns the *raw* (acc, m, l) triple so the caller can fold in the
current token's in-flight KV (which is only committed to the store at the
end of the decode step) with one more online-softmax update.

Layouts: q [BH, R, dh] where BH = B·Hkv and R packs the G = Hq/Hkv grouped
query heads; k/v pages [P, ps, Hkv, dh]; block_table int32 [B, J];
eff_pos int32 [B, J, ps]; q_pos int32 [BH, R].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _page_dequant(codes, scale, kv_dtype):
    """codes [ps, dhp] int32 + scale [ps, 1] -> f32 [ps, dh].  int4
    payloads pack dims d (low nibble) and d + dh//2 (high nibble) into
    byte d, so the unpack is a concat along the head dim
    (kvcache/paged.py)."""
    if kv_dtype == "int4":
        lo = (codes << 28) >> 28              # arithmetic shifts sign-extend
        hi = (codes << 24) >> 28
        codes = jnp.concatenate([lo, hi], axis=-1)
    return codes.astype(jnp.float32) * scale


def _head_slice(blk, sub, dhp: int, hpb: int):
    """blk [ps, hpb·dhp] holds ``hpb`` neighbouring heads; pick head
    ``sub`` (a traced scalar) with static slices and selects."""
    if hpb == 1:
        return blk
    out = blk[:, :dhp]
    for i in range(1, hpb):
        out = jnp.where(sub == i, blk[:, i * dhp:(i + 1) * dhp], out)
    return out


def _paged_kernel(bt_ref, qpos_ref, effpos_ref, q_ref, k_ref, v_ref,
                  *rest, scale: float, n_kv: int, dhp: int, hpb: int,
                  kv_dtype=None):
    if kv_dtype is None:
        ks_ref = vs_ref = None
        o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr = rest
    else:
        (ks_ref, vs_ref, o_ref, m_ref, l_ref,
         m_scr, l_scr, acc_scr) = rest
    head = pl.program_id(0) % n_kv
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def page(ref, s_ref):
        blk = ref[0]                                      # [ps, hpb·dhp]
        if kv_dtype is not None:
            blk = blk.astype(jnp.int32)
        x = _head_slice(blk, head % hpb, dhp, hpb)        # [ps, dhp]
        if kv_dtype is None:
            return x.astype(jnp.float32)
        sc = s_ref[0]                                     # [ps, Hkv]
        lane = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        sc = jnp.sum(jnp.where(lane == head, sc, 0.0), axis=-1,
                     keepdims=True)                       # [ps, 1]
        return _page_dequant(x, sc, kv_dtype)             # in-walk dequant

    q = q_ref[0].astype(jnp.float32) * scale              # [R, dh]
    k = page(k_ref, ks_ref)                               # [ps, dh]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [R, ps]

    kv_pos = effpos_ref[0, 0]                             # [1, ps]
    mask = kv_pos <= qpos_ref[0]                          # [R, ps]
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                   # [R, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
    v = page(v_ref, vs_ref)                               # [ps, dh]
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * alpha + pv
    m_scr[...] = m_new

    @pl.when(j == nj - 1)
    def _finalize():
        # raw triple — the caller merges the in-flight token and divides
        o_ref[0] = acc_scr[...]
        m_ref[0] = m_scr[...]
        l_ref[0] = l_scr[...]


def heads_per_block(n_kv: int, dhp: int) -> int:
    """Neighbouring heads one page block carries: a TPU block's last dim
    must be a multiple of 128 lanes (or the whole row), so heads narrower
    than that (int4-packed dh=128 is 64 bytes) travel in pairs/quads and
    the kernel selects its own."""
    if dhp % 128 == 0 or n_kv == 1:
        return 1
    hpb = 128 // dhp if 128 % dhp == 0 else n_kv
    return hpb if n_kv % hpb == 0 else n_kv


def paged_attention_packed(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray, block_table: jnp.ndarray,
                           eff_pos: jnp.ndarray, q_pos: jnp.ndarray, *,
                           scale: float, interpret: bool = False,
                           k_scales=None, v_scales=None, kv_dtype=None):
    """q: [BH, R, dh]; k/v pages: [P, ps, Hkv, dh]; block_table: [B, J];
    eff_pos: [B, J, ps]; q_pos: [BH, R] (-1 = padded row).

    With a quantized store (``kv_dtype`` "int8"/"int4"), pages hold int8
    codes ([P, ps, Hkv, dh] or nibble-packed [P, ps, Hkv, dh//2]) and
    ``k_scales``/``v_scales`` [P, ps, Hkv] ride the same block-table
    index map — dequantization happens inside the page walk, so HBM
    traffic is the code bytes, never the f32 rows.

    Every block's last two dims are either whole array dims or (8, 128)
    multiples, as the TPU lowering requires: pages are walked as
    [P, ps, Hkv·dhp] rows (a free reshape) in blocks of whole heads,
    and the per-row vectors ride as [.., R, 1] / [.., 1, ps] columns.

    Returns the unnormalized online-softmax state over the paged history:
    (acc [BH, R, dh] f32, m [BH, R] f32, l [BH, R] f32)."""
    BH, R, dh = q.shape
    P, ps, Hkv, dhp = k_pages.shape
    B, J = block_table.shape
    assert BH == B * Hkv, (BH, B, Hkv)
    assert (kv_dtype is None) == (k_scales is None), \
        "quantized pages need kv_dtype AND scales"

    Rp = max(8, R)                       # sublane-friendly row count
    if Rp != R:
        q = jnp.pad(q, ((0, 0), (0, Rp - R), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, Rp - R)), constant_values=-1)
    hpb = heads_per_block(Hkv, dhp)
    width = hpb * dhp

    grid = (BH, J)
    kernel = functools.partial(_paged_kernel, scale=scale, n_kv=Hkv,
                               dhp=dhp, hpb=hpb, kv_dtype=kv_dtype)
    page_spec = pl.BlockSpec(
        (1, ps, width),
        lambda b, j, bt: (bt[b // Hkv, j], 0, (b % Hkv) // hpb))
    row_spec = pl.BlockSpec((1, Rp, 1), lambda b, j, bt: (b, 0, 0))

    in_specs = [
        row_spec,                                                # q_pos
        pl.BlockSpec((1, 1, 1, ps),
                     lambda b, j, bt: (b // Hkv, j, 0, 0)),      # eff_pos
        pl.BlockSpec((1, Rp, dh), lambda b, j, bt: (b, 0, 0)),   # q
        page_spec,                                               # k page
        page_spec,                                               # v page
    ]
    operands = [q_pos[..., None], eff_pos.reshape(B, J, 1, ps), q,
                k_pages.reshape(P, ps, Hkv * dhp),
                v_pages.reshape(P, ps, Hkv * dhp)]
    if kv_dtype is not None:
        scale_spec = pl.BlockSpec((1, ps, Hkv),
                                  lambda b, j, bt: (bt[b // Hkv, j], 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scales, v_scales]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, Rp, dh), lambda b, j, bt: (b, 0, 0)),
            row_spec,
            row_spec,
        ],
        scratch_shapes=[
            pltpu.VMEM((Rp, 1), jnp.float32),    # m
            pltpu.VMEM((Rp, 1), jnp.float32),    # l
            pltpu.VMEM((Rp, dh), jnp.float32),   # acc
        ],
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((BH, Rp, dh), jnp.float32),
            jax.ShapeDtypeStruct((BH, Rp, 1), jnp.float32),
            jax.ShapeDtypeStruct((BH, Rp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(block_table, *operands)
    return acc[:, :R], m[:, :R, 0], l[:, :R, 0]
