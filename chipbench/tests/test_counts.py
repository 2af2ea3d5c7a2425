"""Operation and byte counters against hand counts at tiny sizes."""
import pytest

from chipbench import counts
from chipbench.reference import Dims

# d 8, 2 query heads and 1 KV head of 4, d_ff 16, vocab 10, 3 layers
DM = Dims(layers=3, d=8, hq=2, hkv=1, dh=4, ff=16, vocab=10, eps=1e-6,
          theta=1e4, qk_norm=True)
PEAKS = {"bf16_flops": 100.0, "int8_ops": 200.0, "hbm_bytes_per_s": 10.0}
ALL = ([True] * 3, [True] * 3)              # every router leans to keep


def test_linears_of_one_layer():
    ls = {lin.name: lin for lin in counts.linears(DM)}
    assert (ls["wqkv"].k, ls["wqkv"].n, ls["wqkv"].out) == (8, 16, 16)
    assert (ls["wo"].k, ls["wo"].n, ls["wo"].residual) == (8, 8, True)
    assert (ls["gu"].k, ls["gu"].n, ls["gu"].out) == (8, 32, 16)
    assert (ls["down"].k, ls["down"].n) == (16, 8)


def test_int4_weight_bytes():
    # 256 x 3 codes at half a byte, two groups of 128 rows x 3 scales of 4
    assert counts.int4_weight_bytes(256, 3) == 384 + 24
    # a partial group still has its scale row
    assert counts.int4_weight_bytes(130, 1) == 65 + 8


def test_linear_call():
    lin = counts.Linear("wo", 8, 8, 8, True)
    ops, byts = counts.linear_call(lin, 4)
    assert ops == 2 * 4 * 8 * 8
    # codes 32 + one scale row 32; activations 4 x (8 in + 3 x 8) x 2
    assert byts == 32 + 32 + 4 * 32 * 2


def test_kv_entry_and_weight_bytes():
    assert counts.kv_entry_bytes(DM) == 2 * 1 * 4 * 2
    w = counts.int4_weight_bytes
    per_layer = (w(8, 16) + w(8, 8) + w(8, 32) + w(16, 8)
                 + 2 * 8 * 2 * 4 + 2 * 8 * 2)
    assert counts.model_weight_bytes(DM, ALL) == pytest.approx(
        3 * per_layer + counts.int4_weight_bytes(8, 10))


def test_weight_bytes_leave_out_blocks_leaning_to_skip():
    w = counts.int4_weight_bytes
    attn, mlp = w(8, 16) + w(8, 8), w(8, 32) + w(16, 8)
    lean = ([True, False, True], [False, False, True])
    assert counts.model_weight_bytes(DM, ALL) - counts.model_weight_bytes(
        DM, lean) == pytest.approx(attn + 2 * mlp)
    kept = [(lin.name, n) for lin, n in counts.kept_linears(DM, lean)]
    assert kept == [("wqkv", 2), ("wo", 2), ("gu", 1), ("down", 1)]


def test_row_share_spreads_kept_tokens_over_leaning_layers():
    lean = ([True, False, True], [False, False, True])
    # half of all (token, layer) attention gates open, over 2 layers;
    # a third of the MLP's over 1 layer
    assert counts.row_share(DM, lean, (0.5, 1 / 3)) == pytest.approx(
        (0.75, 1.0))
    assert counts.row_share(DM, lean, (1.0, 1.0)) == (1.0, 1.0)


def test_token_flops_by_hand():
    ctx = 5
    qo, kv, att = 2 * 8 * 8 * 2, 2 * 8 * 2 * 4, 4 * 2 * 4 * ctx
    mlp, router = 2 * 8 * 32 + 2 * 16 * 8, 2 * 2 * 8 * 2
    dense = 3 * (router + qo + kv + att + mlp) + 2 * 8 * 10
    assert counts.token_flops(DM, ctx, 1.0, 1.0, True) == dense
    # nothing kept: routers, and layer 0's K/V
    assert counts.token_flops(DM, ctx, 0.0, 0.0, False) == 3 * router + kv


def test_paged_attention_call():
    ops, byts = counts.paged_attention_call(DM, 100, 2, 1.0)
    assert ops == 4 * 2 * 4 * 100
    assert byts == 100 * 16 + 2 * 2 * 2 * 4 * 2
    half = counts.paged_attention_call(DM, 100, 2, 0.5)
    assert half == pytest.approx((ops / 2, byts / 2))


def test_decode_step_least_time_is_the_larger_bound():
    t = counts.decode_step_least_s(DM, PEAKS, 2, 50, 20, 1.0, 1.0, ALL)
    ops = 2 * counts.token_flops(DM, 10, 1.0, 1.0, True)
    byts = counts.model_weight_bytes(DM, ALL) + 50 * 16
    assert t == pytest.approx(max(ops / 200.0, byts / 10.0))
