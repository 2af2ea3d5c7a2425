"""Unit tests for the loop-aware HLO static cost analyzer (the §Roofline
source of truth)."""
import jax
import jax.numpy as jnp
import pytest

from repro.roofline.hlo_cost import hlo_static_cost
from repro.roofline.analysis import PEAKS, peaks, roofline_terms


def test_scan_flops_match_unrolled():
    def scanned(x, w):
        def body(c, _):
            return c @ w, None
        c, _ = jax.lax.scan(body, x, None, length=7)
        return c

    def unrolled(x, w):
        for _ in range(7):
            x = x @ w
        return x

    sh = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    c1 = hlo_static_cost(jax.jit(scanned).lower(sh, sh).compile().as_text())
    c2 = hlo_static_cost(jax.jit(unrolled).lower(sh, sh).compile().as_text())
    expected = 7 * 2 * 128 ** 3
    assert abs(c1["flops"] - expected) / expected < 0.01
    assert abs(c2["flops"] - expected) / expected < 0.01
    assert c1["unknown_loops"] == 0


def test_nested_scan_multiplication():
    def f(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            ci, _ = jax.lax.scan(inner, c, None, length=3)
            return ci, None
        c, _ = jax.lax.scan(outer, x, None, length=5)
        return c

    sh = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    c = hlo_static_cost(jax.jit(f).lower(sh, sh).compile().as_text())
    expected = 15 * 2 * 64 ** 3
    assert abs(c["flops"] - expected) / expected < 0.02


def test_bf16_upcast_normalization():
    """CPU upcasts bf16 dot operands to f32; bytes must count at bf16."""
    def f(x, w):
        return (x @ w).astype(jnp.bfloat16)

    sh = jax.ShapeDtypeStruct((256, 256), jnp.bfloat16)
    c = hlo_static_cost(jax.jit(f).lower(sh, sh).compile().as_text())
    # reads 2 × 128KB (bf16) + intermediate/result writes; an f32-counted
    # version would be ≥ 4 × that.
    assert c["bytes"] < 1.3e6, c["bytes"]


def test_roofline_terms_bottleneck():
    kind = "TPU v5 lite"
    hw = peaks(kind)
    t = roofline_terms(hw["peak_flops"], 0.0, 0.0, kind)
    assert t["bottleneck"] == "compute" and abs(t["compute_s"] - 1.0) < 1e-9
    t = roofline_terms(0.0, hw["hbm_bw"], 0.0, kind)
    assert t["bottleneck"] == "memory"
    t = roofline_terms(0.0, 0.0, hw["ici_bw"], kind)
    assert t["bottleneck"] == "collective"


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5"])
def test_peaks_unknown_device_kind_raises(kind):
    assert kind not in PEAKS
    with pytest.raises(ValueError, match="no peaks recorded"):
        peaks(kind)
    with pytest.raises(ValueError):
        roofline_terms(1.0, 1.0, 1.0, kind)
