"""INT4 RTN quantization (paper §5.1 / §4.2): error bounds, pow2 scales,
param-tree transformation, end-to-end quantized model."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as M
from repro.quant import dequantize, quantize_params, quantize_rtn

KEY = jax.random.PRNGKey(0)


def test_rtn_roundtrip_error_bound():
    w = jax.random.normal(KEY, (256, 64)) * 0.05
    codes, scale = quantize_rtn(w, 128, pow2_scales=False)
    wd = dequantize(codes, scale)
    # symmetric RTN: |err| <= scale/2 per element
    G = 128
    s_full = np.repeat(np.asarray(scale), G, axis=0)
    assert np.all(np.abs(np.asarray(w) - np.asarray(wd)) <= s_full / 2 + 1e-7)


def test_pow2_scales_are_pow2():
    w = jax.random.normal(KEY, (256, 32))
    _, scale = quantize_rtn(w, 64, pow2_scales=True)
    lg = np.log2(np.asarray(scale))
    np.testing.assert_allclose(lg, np.round(lg), atol=1e-6)


def test_codes_in_int4_range():
    w = jax.random.normal(KEY, (128, 16)) * 3.0
    codes, _ = quantize_rtn(w, 128)
    assert codes.dtype == jnp.int8
    assert int(codes.min()) >= -8 and int(codes.max()) <= 7


def test_quantize_rtn_non_divisible_group_pads():
    """K not a multiple of the group size: the final group is zero-padded
    (masked amax) instead of silently skipping the weight."""
    K, N, G = 200, 16, 128
    w = jax.random.normal(KEY, (K, N)) * 0.05
    codes, scale = quantize_rtn(w, G, pow2_scales=True)
    assert codes.shape == (256, N) and scale.shape == (2, N)
    # padding rows are zero codes: they add nothing to any accumulation
    assert int(jnp.abs(codes[K:]).max()) == 0
    # real rows round-trip within the RTN bound
    wd = dequantize(codes, scale, k=K)
    s_full = np.repeat(np.asarray(scale), G, axis=0)[:K]
    assert np.all(np.abs(np.asarray(w) - np.asarray(wd))
                  <= s_full / 2 * (1 + 1e-5) + 1e-7)
    # and the padded-group amax is the masked amax of the real rows only
    amax_real = np.abs(np.asarray(w[G:], np.float32)).max(axis=0)
    assert np.all(np.asarray(scale[1]) >= amax_real / 7 - 1e-9)


def test_quantize_params_non_divisible_d_ff():
    """A config whose d_ff is not a group multiple must still quantize its
    down-projection (input dim d_ff) — previously silently skipped —
    and the quantized model must run on both dispatch paths."""
    cfg = dataclasses.replace(get_config("llama2-7b").smoke(), d_ff=200)
    params = M.init_params(KEY, cfg)
    # min_size 4k: catches the [200, 128] down-projection but leaves the
    # (tiny) routers dense
    qp = quantize_params(params, group_size=128, min_size=1 << 12)
    down = qp["stack"]["stage0"]["pos0"]["ffn"]["inner"]["down"]
    assert "w_int" in down, "non-divisible d_ff weight was skipped"
    assert down["w_int"].shape[0] == 256          # padded to 2 groups
    toks = jax.random.randint(KEY, (1, 8), 0, cfg.vocab_size)
    lg_j, _, _ = M.prefill(qp, {"tokens": toks}, cfg)
    lg_k, _, _ = M.prefill(qp, {"tokens": toks},
                           dataclasses.replace(cfg, use_kernels=True))
    d = np.asarray(lg_j, np.float32)
    k = np.asarray(lg_k, np.float32)
    assert np.linalg.norm(k - d) / np.linalg.norm(d) < 0.1


def test_quantize_params_structure():
    cfg = get_config("llama2-7b").smoke()
    params = M.init_params(KEY, cfg)
    qp = quantize_params(params, group_size=128, min_size=1 << 12)
    leaves = jax.tree_util.tree_leaves_with_path(qp)
    names = {jax.tree_util.keystr(p) for p, _ in leaves}
    assert any("w_int" in n for n in names)
    assert any("scale" in n for n in names)
    # routers stay unquantized (tiny)
    assert any("router" in n and n.endswith("['w']") for n in names)


def test_quantized_model_close_to_dense():
    cfg = get_config("llama2-7b").smoke()
    params = M.init_params(KEY, cfg)
    qp = quantize_params(params, group_size=64, min_size=1 << 12)
    toks = jax.random.randint(KEY, (1, 16), 0, cfg.vocab_size)
    lg_d, _, _ = M.prefill(params, {"tokens": toks}, cfg)
    lg_q, _, _ = M.prefill(qp, {"tokens": toks}, cfg)
    d = np.asarray(lg_d, np.float32)
    q = np.asarray(lg_q, np.float32)
    # int4 weights perturb logits but preserve the distribution's shape
    corr = np.corrcoef(d.ravel(), q.ravel())[0, 1]
    assert corr > 0.9


# ---------------------------------------------------------------------------
# Layer-by-layer init and quantization of the scan-stacked layers
# ---------------------------------------------------------------------------

def _wide_cfg():
    """Smoke depth, but wide enough that every linear weight of a layer
    (not the routers) clears quantize_params' size threshold."""
    return dataclasses.replace(get_config("llama2-7b").smoke(),
                               d_model=256, d_ff=512)


def _reference_init(key, cfg):
    """The pre-stage-streaming init: one vmap over every stacked stage."""
    from repro.models import layers, transformer
    ks = jax.random.split(key, 4)
    p = {"embed": layers.embedding_init(ks[0], cfg),
         "stack": transformer.stack_init(ks[1], cfg),
         "final_norm": layers.norm_init(cfg.d_model, cfg)}
    if not cfg.tie_embeddings:
        p["lm_head"] = layers.linear_init(ks[2], cfg.d_model,
                                          cfg.vocab_size, cfg, scale=0.02)
    return p


@pytest.mark.parametrize("arch", ["llama2-7b", "qwen3-8b", "gemma3-12b",
                                  "jamba-v0.1-52b"])
def test_stage_streamed_init_matches_vmapped_init(arch):
    cfg = get_config(arch).smoke()
    got = M.init_params(KEY, cfg)
    want = _reference_init(KEY, cfg)
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype, path
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path))


def test_every_stacked_linear_leaf_is_quantized():
    cfg = _wide_cfg()
    assert cfg.num_stages > 1
    qp = M.init_params(KEY, cfg, quantize=True)
    n_stacked = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(qp):
        keys = [getattr(k, "key", "") for k in path]
        if keys[-1] == "w":
            assert keys[-2] == "router", f"dense linear left: {keys}"
        if "stages" in keys and keys[-1] in ("w_int", "scale"):
            n_stacked += 1
            assert leaf.ndim == 3 and leaf.shape[0] == cfg.num_stages - 1
            if keys[-1] == "w_int":
                assert leaf.dtype == jnp.int8
                assert int(leaf.min()) >= -8 and int(leaf.max()) <= 7
    assert n_stacked >= 8         # wqkv, wo, gu, down: codes + scales


def test_quantize_at_init_equals_quantize_params():
    cfg = _wide_cfg()
    a = M.init_params(KEY, cfg, quantize=True)
    b = quantize_params(M.init_params(KEY, cfg), cfg.quant.group_size,
                        cfg.quant.pow2_scales)
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _dequantized_twin(params, dtype):
    """Every {w_int, scale} back to a dense ``w`` (stage axes kept)."""
    if not isinstance(params, dict):
        return params
    if "w_int" in params:
        return {"w": dequantize(params["w_int"], params["scale"]).astype(
            dtype)}
    return {k: _dequantized_twin(v, dtype) for k, v in params.items()}


def test_quantized_model_decodes_like_dequantized_twin():
    """pow2-scaled int4 codes are exact in bf16, so the jnp path of the
    quantized model and of its dequantized twin must pick the same greedy
    tokens — through the stacked layers' per-stage w_int/scale slices."""
    from repro.serve.engine import ContinuousBatchingEngine

    cfg = _wide_cfg()
    qp = M.init_params(KEY, cfg, quantize=True)
    twin = _dequantized_twin(qp, jnp.dtype(cfg.dtype))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (n,), dtype=np.int32)
               for n in (9, 14)]
    tokens = []
    for p in (qp, twin):
        eng = ContinuousBatchingEngine(cfg, p, max_slots=2, max_len=32,
                                       kv_mode="paged")
        uids = [eng.submit(x, 8) for x in prompts]
        res = eng.run()["results"]
        tokens.append([np.asarray(res[u].tokens).tolist() for u in uids])
    assert tokens[0] == tokens[1]
