"""Operations and bytes the work needs, counted from shapes.

Every count is the least that an exact implementation must do, so a
share of a peak stays under 100% for any honest change of the program:
int4 weights count at half a byte per code (plus their float32 scale per
group of 128 input rows), whatever the program stores; a submodule a
router skipped does no work, and a block whose router leans to skipping
(``weights.keep_masks``) reads no weights; a decode step reads each
stored KV entry of each resident once.  ``Dims`` comes from the
configuration file (``reference.dims_of``); ``lean`` is the pair of
[layers] masks of blocks whose routers lean to keeping.
"""
from __future__ import annotations

from typing import NamedTuple

GROUP = 128
ACT_BYTES = 2           # bfloat16 activations and KV


class Linear(NamedTuple):
    name: str
    k: int              # input width
    n: int              # weight columns ([gate | up] counts both halves)
    out: int            # output width
    residual: bool      # the epilogue reads and writes the residual


def linears(dm):
    """The fused-linear calls of one layer: attention's two, then the
    MLP's two."""
    ai, ki = dm.hq * dm.dh, dm.hkv * dm.dh
    return (Linear("wqkv", dm.d, ai + 2 * ki, ai + 2 * ki, False),
            Linear("wo", ai, dm.d, dm.d, True),
            Linear("gu", dm.d, 2 * dm.ff, dm.ff, False),
            Linear("down", dm.ff, dm.d, dm.d, True))


def int4_weight_bytes(k: int, n: int) -> float:
    return k * n / 2 + -(-k // GROUP) * n * 4


def linear_call(lin: Linear, m: int):
    """(ops, bytes) of one fused-linear call on ``m`` rows."""
    ops = 2 * m * lin.k * lin.n
    act = m * (lin.k + lin.out * (3 if lin.residual else 1)) * ACT_BYTES
    return ops, int4_weight_bytes(lin.k, lin.n) + act


def kv_entry_bytes(dm) -> int:
    """Payload of one stored (token, layer) entry: K and V of every KV
    head."""
    return 2 * dm.hkv * dm.dh * ACT_BYTES


def kept_linears(dm, lean):
    """(linear, layers whose router leans to keeping its block) for each
    fused-linear call of a layer."""
    n_attn, n_mlp = int(sum(lean[0])), int(sum(lean[1]))
    return [(lin, n_attn if i < 2 else n_mlp)
            for i, lin in enumerate(linears(dm))]


def row_share(dm, lean, keep):
    """Per block kind, the rows a leaning-to-keep layer's call has to
    compute: the tokens' kept share over all layers (``keep``: attention,
    MLP) spread over the layers that lean to keeping."""
    return tuple(min(1.0, k * dm.layers / max(1, int(sum(m))))
                 for k, m in zip(keep, lean))


def model_weight_bytes(dm, lean) -> float:
    """Bytes of the weights a decode step reads: the linears of the
    blocks whose routers lean to keeping, the output head, and every
    router and norm (float32 and bfloat16)."""
    per_layer = 2 * dm.d * 2 * 4 + 2 * dm.d * ACT_BYTES
    return (dm.layers * per_layer + int4_weight_bytes(dm.d, dm.vocab)
            + sum(n * int4_weight_bytes(lin.k, lin.n)
                  for lin, n in kept_linears(dm, lean)))


def attention_ops(dm, ctx: float) -> float:
    """QK and PV of one query over ``ctx`` keys, every query head."""
    return 4 * dm.hq * dm.dh * ctx


def token_flops(dm, ctx: float, attn_keep: float, mlp_keep: float,
                logits: bool) -> float:
    """Operations one token needs at context ``ctx`` (its own position
    included) with the given shares of attention and MLP blocks kept.
    Layer 0 always computes its K/V (the reuse chain's base)."""
    ai, ki = dm.hq * dm.dh, dm.hkv * dm.dh
    qo = 2 * dm.d * ai * 2
    kv = 2 * dm.d * 2 * ki
    mlp = 2 * dm.d * 2 * dm.ff + 2 * dm.ff * dm.d
    router = 2 * 2 * dm.d * 2
    per_layer_kept = qo + kv + attention_ops(dm, ctx)
    ops = dm.layers * (router + attn_keep * per_layer_kept + mlp_keep * mlp)
    ops += (1 - attn_keep) * kv          # layer 0's K/V when it skips
    if logits:
        ops += 2 * dm.d * dm.vocab
    return ops


def decode_step_least_s(dm, peaks: dict, slots: int, kv_entries: float,
                        ctx_sum: float, attn_keep: float,
                        mlp_keep: float, lean) -> float:
    """Least time of one decode step over ``slots`` tokens whose residents
    hold ``kv_entries`` stored entries and ``ctx_sum`` positions in all:
    the weights of the leaning-to-keep blocks and every stored entry read
    once, against the operations at the int8 peak (the int4 x int8
    matmuls; the attention dots are a small part)."""
    ops = slots * token_flops(dm, ctx_sum / max(slots, 1), attn_keep,
                              mlp_keep, True)
    byts = model_weight_bytes(dm, lean) + kv_entries * kv_entry_bytes(dm)
    return max(ops / peaks["int8_ops"], byts / peaks["hbm_bytes_per_s"])


def paged_attention_call(dm, ctx_sum: float, slots: int, share: float):
    """(ops, bytes) of one layer's paged-attention call over ``slots``
    residents whose contexts sum to ``ctx_sum``, a ``share`` of whose
    queries attend (their gate is open): the entries valid at that layer
    of the attending residents read once, plus q and the output."""
    q_out = 2 * slots * dm.hq * dm.dh * ACT_BYTES
    return (share * attention_ops(dm, ctx_sum),
            share * (ctx_sum * kv_entry_bytes(dm) + q_out))
