"""Fused hybrid linear pipeline: property tests across the prologue ×
weight-path × epilogue matrix vs the ref.py oracles (a deterministic
parametrized grid always runs; hypothesis fuzzes the same checker when
installed), the legacy weight-merge shim, and end-to-end
``use_kernels=True`` ≡ pure-jnp decode identity for the dense and int4
engines (interpret mode on CPU)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import routing
from repro.kernels import ops, ref
from repro.kernels import fused_linear as fl
from repro.kernels.fused_linear import fused_linear_pallas, fused_linear_plan
from repro.models import layers
from repro.models import model as M
from repro.quant import quantize_params, quantize_rtn

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                        # pragma: no cover
    HAVE_HYPOTHESIS = False

KEY = jax.random.PRNGKey(0)


def _mx(a, b):
    return float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())


# ---------------------------------------------------------------------------
# Property checker: kernel == oracle over the full configuration matrix
# ---------------------------------------------------------------------------

def _check_case(seed: int, M_: int, K: int, F: int, prologue: bool,
                int4: bool, epilogue: str, act, group: int = 64,
                tiles=None):
    """Kernel against oracle; ``tiles`` = (bm, bn, bk) calls the kernel
    with those tiles in place of the planned ones."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((M_, K)),
                    jnp.float32).astype(jnp.bfloat16)
    N = 2 * F if epilogue == "glu" else F
    w = jnp.asarray(rng.standard_normal((K, N)) * 0.04, jnp.float32)
    kw = {"act": act}
    if prologue:
        kw["mean_sq"] = jnp.asarray(
            (np.asarray(x, np.float32) ** 2).mean(-1))
        kw["gamma"] = jnp.asarray(
            1.0 + 0.1 * rng.standard_normal(K), jnp.float32)
    if epilogue == "glu":
        kw["glu"] = True
    if epilogue == "residual":
        kw["residual"] = jnp.asarray(
            rng.standard_normal((M_, F)), jnp.float32).astype(jnp.bfloat16)
        kw["gate_mul"] = jnp.asarray(
            (rng.random(M_) > 0.5).astype(np.float32))
        kw["emit_sq"] = True

    if int4:
        codes, scale = quantize_rtn(w, group, pow2_scales=True)
        params = {"w_int": codes, "scale": scale}
        args = dict(w_codes=codes, scale=scale)
    else:
        params = {"w": w}
        args = dict(w=w)

    if tiles is None:
        out, sq = ops.fused_linear(params, x, **kw)
    else:
        bm, bn, bk = tiles
        out, sq = fused_linear_pallas(x, **args, **kw, bm=bm, bn=bn, bk=bk,
                                      interpret=True)
    oref, sq_ref = ref.fused_linear_ref(x, **args, **kw)
    scale_mag = max(1.0, float(jnp.abs(oref.astype(jnp.float32)).max()))
    assert _mx(out, oref) <= 1e-4 * scale_mag
    if sq_ref is not None:
        np.testing.assert_allclose(np.asarray(sq), np.asarray(sq_ref),
                                   rtol=1e-4, atol=1e-4)
    else:
        assert sq is None


_GRID = [
    # seed, M, K, F, prologue, int4, epilogue, act, group
    (0, 37, 300, 70, True, False, "glu", "silu", 64),
    (1, 64, 128, 32, True, True, "glu", "gelu", 128),
    (2, 7, 200, 130, False, True, "residual", None, 32),
    (3, 48, 256, 96, True, True, "residual", "silu", 64),
    (4, 1, 64, 32, False, False, "none", None, 64),
    (5, 70, 300, 96, True, True, "none", "gelu", 128),
    (6, 33, 64, 130, False, False, "residual", "gelu", 64),
    (7, 16, 200, 32, True, False, "none", None, 64),
    # an odd [gate | up] taken whole as one joint block: padded to
    # 128-wide tiles, its dense dot misses the bound
    (2625, 58, 300, 130, False, False, "glu", "gelu", 32),
]


@pytest.mark.parametrize("seed,M_,K,F,prologue,int4,epilogue,act,group",
                         _GRID)
def test_fused_linear_matches_oracle_grid(seed, M_, K, F, prologue, int4,
                                          epilogue, act, group):
    _check_case(seed, M_, K, F, prologue, int4, epilogue, act, group)


if HAVE_HYPOTHESIS:
    @given(st.data())
    @settings(max_examples=12, deadline=None)
    def test_fused_linear_matches_oracle_fuzz(data):
        epilogue = data.draw(st.sampled_from(["none", "glu", "residual"]))
        act = (data.draw(st.sampled_from(["silu", "gelu"]))
               if epilogue == "glu"
               else data.draw(st.sampled_from([None, "silu", "gelu"])))
        _check_case(
            seed=data.draw(st.integers(0, 10_000)),
            M_=data.draw(st.integers(1, 70)),
            K=data.draw(st.sampled_from([64, 128, 200, 300])),
            F=data.draw(st.sampled_from([32, 96, 130])),
            prologue=data.draw(st.booleans()),
            int4=data.draw(st.booleans()),
            epilogue=epilogue, act=act,
            group=data.draw(st.sampled_from([32, 64, 128])))


# K steps of several quantization groups, several K steps and column
# blocks (the scale row of group g in step k is k·(bk/G) + g), row blocks
# with padding, and the Σy² carry across column blocks
_MULTIGROUP = [
    # seed, M, K, F, prologue, epilogue, act, group, tiles (None: planned)
    (10, 16, 1024, 256, True, "glu", "silu", 128, None),
    (11, 40, 1024, 384, False, "residual", None, 128, (40, 128, 256)),
    (12, 24, 768, 256, True, "glu", "gelu", 64, (24, 128, 384)),
    (13, 300, 512, 128, True, "none", "silu", 128, (128, 128, 256)),
    (14, 16, 2048, 512, True, "residual", "gelu", 128, None),
]


@pytest.mark.parametrize("seed,M_,K,F,prologue,epilogue,act,group,tiles",
                         _MULTIGROUP)
def test_multigroup_k_steps_match_oracle(seed, M_, K, F, prologue, epilogue,
                                         act, group, tiles):
    if tiles is None:
        plan = fused_linear_plan(M_, K, F, group, epilogue == "glu", True)
        assert plan.bk > group
    else:
        assert tiles[2] > group
    _check_case(seed, M_, K, F, prologue, True, epilogue, act, group,
                tiles=tiles)


_BITWISE = [
    # M, K, F, epilogue
    (16, 1024, 512, "glu"),
    (16, 1024, 640, "residual"),
    (64, 2048, 256, "none"),
]


@pytest.mark.parametrize("M_,K,F,epilogue", _BITWISE)
def test_planned_int4_tiles_equal_one_group_tiles(M_, K, F, epilogue):
    """Several groups per K step keep each (row, group)'s arithmetic and
    the order of the f32 accumulation, and Σy² adds 128-column chunks in
    column order: output and Σy² equal those of 128-wide column tiles
    with one group per K step bit for bit."""
    G = 128
    rng = np.random.default_rng(M_ + K + F)
    glu = epilogue == "glu"
    x = jnp.asarray(rng.standard_normal((M_, K)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((K, 2 * F if glu else F)) * 0.04,
                    jnp.float32)
    codes, scale = quantize_rtn(w, G, pow2_scales=True)
    kw = dict(w_codes=codes, scale=scale, glu=glu,
              act="silu" if glu else None, interpret=True,
              mean_sq=jnp.asarray((np.asarray(x, np.float32) ** 2).mean(-1)),
              gamma=jnp.asarray(1 + 0.1 * rng.standard_normal(K),
                                jnp.bfloat16))
    if epilogue == "residual":
        kw.update(residual=jnp.asarray(rng.standard_normal((M_, F)),
                                       jnp.bfloat16),
                  gate_mul=jnp.asarray((rng.random(M_) > 0.3), jnp.float32),
                  emit_sq=True)
    plan = fused_linear_plan(M_, K, F, G, glu, True)
    assert plan.bk > G and plan.bn > 128
    out, sq = fused_linear_pallas(x, **kw)
    old, sq_old = fused_linear_pallas(x, **kw, bm=128, bn=128, bk=G)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(old, np.float32))
    if epilogue == "residual":
        np.testing.assert_array_equal(np.asarray(sq), np.asarray(sq_old))


# ---------------------------------------------------------------------------
# The tile plan at the benchmark configurations' widths
# ---------------------------------------------------------------------------

def _primitives(jaxpr) -> set:
    """Primitive names of a jaxpr and of the jaxprs nested in it (``jnp.pad``
    traces as a ``jit`` around ``pad``), the Pallas kernel bodies
    excepted."""
    out = set()
    for e in jaxpr.eqns:
        out.add(e.primitive.name)
        if e.primitive.name == "pallas_call":
            continue
        for v in e.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out |= _primitives(inner)
    return out


def _linears(arch: str):
    """(name, K, F, glu) of the four fused-linear calls of one layer."""
    cfg = get_config(arch)
    d, ai, ki, ff = (cfg.d_model, cfg.attn_inner_dim, cfg.kv_inner_dim,
                     cfg.d_ff)
    return [("wqkv", d, ai + 2 * ki, False), ("wo", ai, d, False),
            ("gu", d, ff, True), ("down", ff, d, False)]


@pytest.mark.parametrize("M_", [16, 256, 1024, 2048])
@pytest.mark.parametrize("arch,lin", [(a, i) for a in ("qwen3-8b",
                                                        "deepseek-coder-33b")
                                      for i in range(4)])
def test_plan_tiles_divide_fit_and_never_pad(arch, lin, M_):
    name, K, F, glu = _linears(arch)[lin]
    G = 128
    p = fused_linear_plan(M_, K, F, G, glu, True)
    assert not p.fallback, (name, p)
    assert K % p.bk == 0 and p.bk % G == 0 and p.bk % fl.LANE == 0
    assert F % p.bn == 0 and p.bn % fl.LANE == 0
    assert M_ % p.bm == 0 and p.bm <= fl.BM_MAX
    assert p.bk * p.bn <= fl.W_TILE_MAX
    assert p.vmem_bytes <= fl.VMEM_BUDGET
    assert p.grid == (M_ // p.bm, F // p.bn, K // p.bk)
    # one grid step moves a weight tile of at least 1 MiB per half
    assert p.bk * p.bn >= 1 << 20, (name, p)
    # the call as traced pads nothing: no weight (nor x) is copied
    N = 2 * F if glu else F
    args = (jax.ShapeDtypeStruct((M_, K), jnp.bfloat16),
            jax.ShapeDtypeStruct((K, N), jnp.int8),
            jax.ShapeDtypeStruct((K // G, N), jnp.float32),
            jax.ShapeDtypeStruct((M_,), jnp.float32),
            jax.ShapeDtypeStruct((K,), jnp.bfloat16))

    def call(x, codes, scale, ms, gamma):
        return fused_linear_pallas(x, w_codes=codes, scale=scale,
                                   mean_sq=ms, gamma=gamma, glu=glu,
                                   act="silu" if glu else None)

    prims = _primitives(jax.make_jaxpr(call)(*args).jaxpr)
    assert "pad" not in prims and "pallas_call" in prims


_FALLBACK = [
    # M, K, F, G, glu, int4, bn, bk, pads the weight
    (16, 256, 130, 128, False, True, 130, 256, False),   # whole odd F
    (16, 256, 130, 128, True, True, 130, 256, False),    # joint [gate|up]
    (16, 300, 70, 1, False, False, 70, 300, False),      # dense, odd K
    (16, 4096, 2100, 128, False, True, 128, 2048, True),  # F over BN_MAX
    (16, 3000, 256, 1, False, False, 256, 512, True),    # K over BK_MAX
]


@pytest.mark.parametrize("M_,K,F,G,glu,int4,bn,bk,pads", _FALLBACK)
def test_plan_fallback_for_unaligned_axes(M_, K, F, G, glu, int4, bn, bk,
                                          pads):
    """An axis with no lane-aligned dividing tile counts as a fallback:
    taken whole where it fits (no padding), else in today's 128-wide
    column tiles or 512-row K steps (int4: one group), padded; the other
    axis keeps its planned tile."""
    p = fused_linear_plan(M_, K, F, G, glu, int4,
                          w_bytes=1 if int4 else 4)
    assert p.fallback and (p.bn, p.bk) == (bn, bk)
    assert not fused_linear_plan(M_, 4096, 4096, 128, glu, True).fallback
    N = 2 * F if glu else F
    w = ((jax.ShapeDtypeStruct((K, N), jnp.int8),
          jax.ShapeDtypeStruct((K // G, N), jnp.float32)) if int4
         else (jax.ShapeDtypeStruct((K, N), jnp.float32),))

    def call(x, *w):
        args = dict(w_codes=w[0], scale=w[1]) if int4 else dict(w=w[0])
        return fused_linear_pallas(x, **args, glu=glu, act=None)

    prims = _primitives(jax.make_jaxpr(call)(
        jax.ShapeDtypeStruct((M_, K), jnp.bfloat16), *w).jaxpr)
    assert ("pad" in prims) == pads


def test_engine_records_the_tiles_each_call_shape_traced_with():
    """The engine's registry states the plan of every fused-linear call
    shape its jitted steps traced, under the step's name, and how many
    of them fell back."""
    from repro.serve.config import EngineConfig, KVConfig, SchedulingConfig
    from repro.serve.engine import ContinuousBatchingEngine

    cfg = dataclasses.replace(get_config("llama2-7b").smoke(),
                              use_kernels=True)
    params = quantize_params(M.init_params(KEY, cfg), group_size=64,
                             min_size=1 << 10)
    eng = ContinuousBatchingEngine(cfg, params, config=EngineConfig(
        kv=KVConfig(kv_mode="paged", page_size=8, num_pages=32),
        scheduling=SchedulingConfig(max_slots=2, max_len=32,
                                    decode_steps=2)))
    rng = np.random.default_rng(0)
    for n in (9, 13):
        eng.submit(rng.integers(0, cfg.vocab_size, (n,), dtype=np.int32),
                   max_new_tokens=3)
    eng.run()
    plans = eng._linear_plans
    phases = {phase for _, phase in plans}
    assert {"loop_fn", "prefill_paged_fn"} <= phases, phases
    m = eng.metrics
    for (call, phase), plan in plans.items():
        rows, K, N = map(int, call.split("x"))
        assert plan.grid[0] == -(-rows // plan.bm)
        assert m.value("fused_linear_grid_steps", call=call,
                       phase=phase) == plan.steps
    # decode calls run every slot's row: M = max_slots
    assert {int(c.split("x")[0]) for c, p in plans if p == "loop_fn"} == {2}
    assert m.value("fused_linear_plan_fallbacks_total", default=-1) == sum(
        p.fallback for p in plans.values())


def test_fused_linear_leading_dims_and_jnp_dispatch():
    """[B, T, K] leading dims flatten/unflatten; use_kernel=False routes
    to the same oracle arithmetic."""
    rng = np.random.default_rng(7)
    B, T, K, F = 2, 5, 96, 40
    x = jnp.asarray(rng.standard_normal((B, T, K)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((K, F)) * 0.05, jnp.float32)
    res = jnp.asarray(rng.standard_normal((B, T, F)), jnp.float32)
    gm = jnp.asarray((rng.random((B, T)) > 0.5).astype(np.float32))
    ok, sqk = ops.fused_linear({"w": w}, x, residual=res, gate_mul=gm,
                               emit_sq=True, use_kernel=True)
    oj, sqj = ops.fused_linear({"w": w}, x, residual=res, gate_mul=gm,
                               emit_sq=True, use_kernel=False)
    assert ok.shape == (B, T, F) and sqk.shape == (B, T)
    np.testing.assert_allclose(np.asarray(ok), np.asarray(oj),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(sqk), np.asarray(sqj),
                               rtol=1e-5, atol=1e-5)


def test_emitted_sq_equals_next_norm_stats():
    """The epilogue's Σy²/D carry must equal the next block's norm_stats
    reduction of the written residual stream (fp32, pre-cast)."""
    ks = jax.random.split(KEY, 3)
    M_, K, F = 33, 128, 128
    x = jax.random.normal(ks[0], (M_, K), jnp.float32)
    w = jax.random.normal(ks[1], (K, F), jnp.float32) * 0.05
    res = jax.random.normal(ks[2], (M_, F), jnp.float32)
    out, sq = ops.fused_linear({"w": w}, x, residual=res, emit_sq=True)
    cfg = get_config("qwen3-8b").smoke()
    direct = layers.norm_stats(out, cfg)          # rmsnorm: mean(y²)
    np.testing.assert_allclose(np.asarray(sq) / F, np.asarray(direct),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Legacy weight-merge shim
# ---------------------------------------------------------------------------

def test_merge_legacy_linear_params():
    cfg = get_config("qwen3-8b").smoke()
    ks = jax.random.split(KEY, 5)
    d, ai, ki, f = (cfg.d_model, cfg.attn_inner_dim, cfg.kv_inner_dim,
                    cfg.d_ff)
    legacy = {
        "mixer": {"inner": {
            "wq": layers.linear_init(ks[0], d, ai, cfg),
            "wk": layers.linear_init(ks[1], d, ki, cfg),
            "wv": layers.linear_init(ks[2], d, ki, cfg),
            "wo": layers.linear_init(ks[3], ai, d, cfg)}},
        "ffn": {"inner": {
            "gate": layers.linear_init(ks[4], d, f, cfg),
            "up": layers.linear_init(ks[0], d, f, cfg),
            "down": layers.linear_init(ks[1], f, d, cfg)}},
    }
    merged = layers.merge_legacy_linear_params(legacy)
    inner = merged["mixer"]["inner"]
    assert set(inner) == {"wqkv", "wo"}
    assert inner["wqkv"]["w"].shape == (d, ai + 2 * ki)
    np.testing.assert_array_equal(
        np.asarray(inner["wqkv"]["w"][:, :ai]),
        np.asarray(legacy["mixer"]["inner"]["wq"]["w"]))
    ffn = merged["ffn"]["inner"]
    assert set(ffn) == {"gu", "down"}
    np.testing.assert_array_equal(
        np.asarray(ffn["gu"]["w"][:, f:]),
        np.asarray(legacy["ffn"]["inner"]["up"]["w"]))
    assert layers.mlp_fusable(ffn)


def test_merge_legacy_mixed_quantization():
    """quantize_params' size threshold can quantize wq but leave the
    smaller wk/wv dense on a legacy GQA tree — the merge shim must
    dequantize the mixed trio into a dense wqkv instead of crashing."""
    rng = np.random.default_rng(5)
    d, ai, ki = 64, 64, 16
    wq = jnp.asarray(rng.standard_normal((d, ai)) * 0.05, jnp.float32)
    codes, scale = quantize_rtn(wq, 32, pow2_scales=True)
    legacy = {"inner": {
        "wq": {"w_int": codes, "scale": scale},
        "wk": {"w": jnp.asarray(rng.standard_normal((d, ki)) * 0.05,
                                jnp.float32)},
        "wv": {"w": jnp.asarray(rng.standard_normal((d, ki)) * 0.05,
                                jnp.float32)},
        "wo": {"w": jnp.asarray(rng.standard_normal((ai, d)) * 0.05,
                                jnp.float32)}}}
    merged = layers.merge_legacy_linear_params(legacy)["inner"]
    assert set(merged) == {"wqkv", "wo"}
    assert merged["wqkv"]["w"].shape == (d, ai + 2 * ki)
    # the quantized slice round-trips through dequantization
    from repro.quant import dequantize
    np.testing.assert_allclose(np.asarray(merged["wqkv"]["w"][:, :ai]),
                               np.asarray(dequantize(codes, scale, k=d)),
                               rtol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(merged["wqkv"]["w"][:, ai:ai + ki]),
        np.asarray(legacy["inner"]["wk"]["w"]))


def test_quantized_merged_weights_slice_consistently():
    """Slicing a quantized merged wqkv must equal quantizing the slices:
    per-group scales are per-output-column, so the BFP domain commutes
    with the column split."""
    rng = np.random.default_rng(3)
    d, ai, ki = 128, 128, 64
    w = jnp.asarray(rng.standard_normal((d, ai + 2 * ki)) * 0.05,
                    jnp.float32)
    codes, scale = quantize_rtn(w, 64, pow2_scales=True)
    merged = {"w_int": codes, "scale": scale}
    sliced = layers.slice_linear(merged, ai, ai + ki)
    codes_k, scale_k = quantize_rtn(w[:, ai:ai + ki], 64, pow2_scales=True)
    np.testing.assert_array_equal(np.asarray(sliced["w_int"]),
                                  np.asarray(codes_k))
    np.testing.assert_array_equal(np.asarray(sliced["scale"]),
                                  np.asarray(scale_k))


# ---------------------------------------------------------------------------
# End-to-end decode identity: use_kernels=True ≡ pure-jnp
# ---------------------------------------------------------------------------

def _greedy_decode(params, cfg, toks, steps=3, forced=None):
    """Prefill + ``steps`` decode steps.  ``forced`` [B, steps] pins the
    fed tokens (teacher forcing) so different numeric paths stay aligned;
    otherwise each step feeds its own argmax."""
    T = toks.shape[1]
    lg, cache, _ = M.prefill(params, {"tokens": toks}, cfg, pad_to=T + steps)
    logits = [lg]
    tok = lg.argmax(-1)[:, None] if forced is None else forced[:, :1]
    for s in range(steps):
        lg, cache, _ = M.decode_step(params, cache, {"tokens": tok},
                                     jnp.int32(T + s), cfg)
        logits.append(lg)
        if forced is None:
            tok = lg.argmax(-1)[:, None]
        elif s + 1 < steps:
            tok = forced[:, s + 1:s + 2]
    return logits


@pytest.mark.parametrize("mode", ["masked", "gather"])
def test_decode_identity_dense_engine(mode):
    base = get_config("qwen3-8b").smoke()
    base = dataclasses.replace(
        base, skip=dataclasses.replace(base.skip, mode=mode))
    params = routing.neutral_router_bias(M.init_params(KEY, base))
    toks = jax.random.randint(KEY, (2, 24), 0, base.vocab_size)
    lj = _greedy_decode(params, dataclasses.replace(base, use_kernels=False),
                        toks)
    lk = _greedy_decode(params, dataclasses.replace(base, use_kernels=True),
                        toks)
    for a, b in zip(lj, lk):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=0.05, atol=0.05)
        assert (a.argmax(-1) == b.argmax(-1)).all()


def test_decode_identity_int4_engine():
    """int4 engine: the fused kernel path must stay inside the BFP-regime
    tolerance of the exact-dequant jnp path, and restructuring the
    dispatch (fuse_linear on/off, both on the kernel path) must not move
    the greedy tokens."""
    base = get_config("qwen3-8b").smoke()
    params = quantize_params(M.init_params(KEY, base), group_size=64,
                             min_size=1 << 12)
    toks = jax.random.randint(KEY, (2, 24), 0, base.vocab_size)
    # teacher-forced continuation keeps the three numeric paths aligned
    # (self-fed greedy would diverge after any BFP-noise tie-break and
    # make later logits incomparable)
    forced = jax.random.randint(jax.random.PRNGKey(9), (2, 3), 0,
                                base.vocab_size)
    lj = _greedy_decode(params, dataclasses.replace(base, use_kernels=False),
                        toks, forced=forced)
    lk = _greedy_decode(params, dataclasses.replace(base, use_kernels=True),
                        toks, forced=forced)
    lu = _greedy_decode(params, dataclasses.replace(
        base, use_kernels=True, fuse_linear=False), toks, forced=forced)
    agree, total = 0, 0
    for a, b, c in zip(lj, lk, lu):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        c = np.asarray(c, np.float32)
        # kernel (BFP fixed-point) vs jnp (exact dequant): Table-1 regime
        assert np.linalg.norm(b - a) / np.linalg.norm(a) < 0.1
        # fused vs per-op kernel dispatch: same arithmetic domain
        assert np.linalg.norm(b - c) / np.linalg.norm(c) < 0.1
        # near-ties may flip under BFP rounding: require the fused pick to
        # sit in the unfused top-5 (and mostly agree exactly)
        top5_c = np.argsort(c, axis=-1)[:, -5:]
        for row, pick in enumerate(b.argmax(-1)):
            assert pick in top5_c[row]
        agree += int((b.argmax(-1) == c.argmax(-1)).sum())
        total += b.shape[0]
    assert agree / total >= 0.75, f"argmax agreement {agree}/{total}"


# The paged-decode fused prologue is covered end-to-end by
# tests/test_paged_kv.py::test_paged_decode_matches_dense_and_compact_store
# with use_kernels=True, which now dispatches through the fused pipeline
# (cfg.fuse_linear defaults on).
