"""Ahead-of-time compiles of the serving path's Pallas kernels for a TPU
v5e, at llama2-7b's published widths (d_model 4096, d_ff 11008, 32 heads
of 128, int4 group 128, page size 16).

Interpret mode runs the kernel bodies on the CPU but says nothing about
whether Mosaic accepts their block shapes or fits them in VMEM.  These
tests hand each kernel to the TPU compiler against a *described*
``v5e:2x2`` topology (no chip needed), so a tiling or VMEM refusal fails
here instead of on the chip.  Nothing runs: only ``.lower().compile()``.

The topology is described inside a module-scoped fixture (never at import
time): only one process may hold the TPU library, and a test worker that
loads it at collection would starve the others.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention_packed
from repro.kernels.fused_linear import fused_linear_pallas
from repro.kernels.fused_router_rmsnorm import router_stats_pallas
from repro.kernels.int4_matmul import int4_matmul_pallas
from repro.kernels.paged_attention import paged_attention_packed

D_MODEL, D_FF, HEADS, HEAD_DIM, GROUP = 4096, 11008, 32, 128, 128
PAGE_SIZE, PAGES, SLOTS, PAGES_PER_SLOT = 16, 1024, 4, 64
DECODE_M, PREFILL_T = 4, 1024


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the
    # persistent cache without one; keep them out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, sharding, *shapes):
    """Lower + compile ``fn`` for the described chip; return the HLO text
    so callers can check the Mosaic kernel is really in the program."""
    args = [_spec(sharding, s, d) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return compiled


@pytest.mark.parametrize("kv_dtype", [None, "int8", "int4"])
def test_paged_attention_compiles(one_chip, kv_dtype):
    dhp = HEAD_DIM // 2 if kv_dtype == "int4" else HEAD_DIM
    page_dt = jnp.bfloat16 if kv_dtype is None else jnp.int8
    shapes = [((SLOTS * HEADS, 1, HEAD_DIM), jnp.bfloat16),
              ((PAGES, PAGE_SIZE, HEADS, dhp), page_dt),
              ((PAGES, PAGE_SIZE, HEADS, dhp), page_dt),
              ((SLOTS, PAGES_PER_SLOT), jnp.int32),
              ((SLOTS, PAGES_PER_SLOT, PAGE_SIZE), jnp.int32),
              ((SLOTS * HEADS, 1), jnp.int32)]
    if kv_dtype is not None:
        shapes += [((PAGES, PAGE_SIZE, HEADS), jnp.float32)] * 2

    def fn(q, kp, vp, bt, eff, qpos, *scales):
        ks, vs = scales if scales else (None, None)
        return paged_attention_packed(q, kp, vp, bt, eff, qpos, scale=0.088,
                                      k_scales=ks, v_scales=vs,
                                      kv_dtype=kv_dtype)

    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("m", [DECODE_M, PREFILL_T])
def test_fused_linear_int4_gate_up_compiles(one_chip, m):
    """Norm prologue × int4 [gate|up] 4096→2×11008 × SwiGLU epilogue."""
    def fn(x, ms, gamma, codes, scale):
        return fused_linear_pallas(x, w_codes=codes, scale=scale,
                                   mean_sq=ms, gamma=gamma, glu=True,
                                   act="silu")[0]

    _compile(fn, one_chip,
             ((m, D_MODEL), jnp.bfloat16), ((m,), jnp.float32),
             ((D_MODEL,), jnp.bfloat16),
             ((D_MODEL, 2 * D_FF), jnp.int8),
             ((D_MODEL // GROUP, 2 * D_FF), jnp.float32))


@pytest.mark.parametrize("m", [DECODE_M, PREFILL_T])
def test_fused_linear_int4_down_compiles(one_chip, m):
    """int4 down 11008→4096 with gate-multiplier, residual and Σy²."""
    def fn(x, codes, scale, res, gm):
        return fused_linear_pallas(x, w_codes=codes, scale=scale,
                                   residual=res, gate_mul=gm, emit_sq=True)

    _compile(fn, one_chip,
             ((m, D_FF), jnp.bfloat16),
             ((D_FF, D_MODEL), jnp.int8),
             ((D_FF // GROUP, D_MODEL), jnp.float32),
             ((m, D_MODEL), jnp.bfloat16), ((m,), jnp.float32))


def _bench_linears():
    """(arch, name, K, N, kind) of the benchmark configurations' four
    fused-linear calls per layer, as the model makes them."""
    from repro.configs import get_config
    out = []
    for arch in ("qwen3-8b", "deepseek-coder-33b"):
        cfg = get_config(arch)
        d, ai, ki, ff = (cfg.d_model, cfg.attn_inner_dim, cfg.kv_inner_dim,
                         cfg.d_ff)
        out += [(arch, "wqkv", d, ai + 2 * ki, "norm"),
                (arch, "wo", ai, d, "res"),
                (arch, "gu", d, 2 * ff, "glu"),
                (arch, "down", ff, d, "res")]
    return out


@pytest.mark.parametrize("m", [16, 2048])
@pytest.mark.parametrize("lin", range(8))
def test_fused_linear_planned_tiles_compile(one_chip, lin, m):
    """The tiles ``fused_linear_plan`` picks at the benchmark widths (decode
    rows and 512-row prefill blocks) fit the chip's scoped VMEM."""
    _, _, K, N, kind = _bench_linears()[lin]
    glu = kind == "glu"
    shapes = [((m, K), jnp.bfloat16), ((K, N), jnp.int8),
              ((K // GROUP, N), jnp.float32)]
    if kind == "res":
        shapes += [((m, N), jnp.bfloat16), ((m,), jnp.float32)]

        def fn(x, codes, scale, res, gm):
            return fused_linear_pallas(x, w_codes=codes, scale=scale,
                                       residual=res, gate_mul=gm,
                                       emit_sq=True)
    else:
        shapes += [((m,), jnp.float32), ((K,), jnp.bfloat16)]

        def fn(x, codes, scale, ms, gamma):
            return fused_linear_pallas(x, w_codes=codes, scale=scale,
                                       mean_sq=ms, gamma=gamma, glu=glu,
                                       act="silu" if glu else None)[0]

    _compile(fn, one_chip, *shapes)


def test_int4_matmul_compiles(one_chip):
    """The unfused int4 path: the 32000-wide lm_head at decode M."""
    def fn(x, codes, scale):
        return int4_matmul_pallas(x, codes, scale)

    _compile(fn, one_chip,
             ((DECODE_M, D_MODEL), jnp.bfloat16),
             ((D_MODEL, 32000), jnp.int8),
             ((D_MODEL // GROUP, 32000), jnp.float32))


def test_fused_router_rmsnorm_compiles(one_chip):
    def fn(x, w):
        return router_stats_pallas(x, w)

    _compile(fn, one_chip, ((PREFILL_T, D_MODEL), jnp.bfloat16),
             ((D_MODEL, 2), jnp.bfloat16))


def test_flash_attention_prefill_compiles(one_chip):
    def fn(q, k, v, qpos, kvlen):
        return flash_attention_packed(q, k, v, qpos, kvlen, causal=True,
                                      scale=0.088)

    bh = HEADS
    _compile(fn, one_chip,
             ((bh, PREFILL_T, HEAD_DIM), jnp.bfloat16),
             ((bh, PREFILL_T, HEAD_DIM), jnp.bfloat16),
             ((bh, PREFILL_T, HEAD_DIM), jnp.bfloat16),
             ((bh, PREFILL_T), jnp.int32), ((bh, 1), jnp.int32))
