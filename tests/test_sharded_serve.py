"""Tensor-parallel sharded serving: the continuous-batching engine under an
active serve-mode ``ShardingPolicy``.

Runs IN-PROCESS against however many devices this process sees — the
multi-device CI job provides 8 simulated host devices via
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` and sets
``REQUIRE_MULTIDEVICE=1`` so these tests FAIL (not skip) if the topology
is missing; on a plain 1-device host (tier-1) they skip.

The acceptance bar is *token identity*: the sharded engine must emit
bit-identical token ids to the unsharded engine for dense and paged KV
modes, with chunked prefill and under forced preemption — sharding is a
pure layout change; the scheduler, allocator and history indirection stay
host-side and replicated.
"""
import dataclasses
import os

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.routing import neutral_router_bias
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.serve.engine import ContinuousBatchingEngine

KEY = jax.random.PRNGKey(0)
REQUIRED = 8


def _need_devices(n: int = REQUIRED) -> None:
    have = jax.device_count()
    if have >= n:
        return
    if os.environ.get("REQUIRE_MULTIDEVICE"):
        pytest.fail(
            f"REQUIRE_MULTIDEVICE is set but only {have} device(s) are "
            f"visible — the CI job must export XLA_FLAGS="
            f"--xla_force_host_platform_device_count={REQUIRED}")
    pytest.skip(f"needs {n} devices (got {have}); set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={REQUIRED}")


def _cfg(**over):
    # 8 query/KV heads so the head axis splits cleanly over model=8
    cfg = dataclasses.replace(get_config("llama2-7b").smoke(),
                              num_heads=8, num_kv_heads=8, head_dim=16)
    return dataclasses.replace(cfg, **over) if over else cfg


def _params(cfg):
    return neutral_router_bias(M.init_params(KEY, cfg))


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (l,), dtype=np.int32)
            for l in lens]


def _axes(spec):
    """Flatten a PartitionSpec into the mesh axis names it uses."""
    out = []
    for ax in spec:
        if ax is None:
            continue
        out.extend(ax if isinstance(ax, tuple) else (ax,))
    return out


def _run_pair(cfg, params, prompts, mesh, max_new=10, **kw):
    """Run the same workload unsharded and sharded; return both outputs."""
    outs = []
    for m in (None, mesh):
        eng = ContinuousBatchingEngine(cfg, params, mesh=m, **kw)
        for p in prompts:
            eng.submit(p, max_new_tokens=max_new)
        outs.append((eng, eng.run()))
    return outs


def _assert_identical(base, shard):
    _, ob = base
    _, os_ = shard
    assert set(ob["results"]) == set(os_["results"])
    for uid in ob["results"]:
        b, s = ob["results"][uid], os_["results"][uid]
        np.testing.assert_array_equal(b.tokens, s.tokens)
        assert b.finish_reason == s.finish_reason
        assert (b.kv_stored, b.kv_dense) == (s.kv_stored, s.kv_dense)
    sb, ss = ob["stats"], os_["stats"]
    assert sb.decode_tokens == ss.decode_tokens
    assert sb.prefill_tokens == ss.prefill_tokens
    assert sb.requests_completed == ss.requests_completed
    assert sb.preemptions == ss.preemptions


@pytest.mark.slow
def test_dense_sharded_identity_tp8():
    _need_devices()
    cfg = _cfg()
    params = _params(cfg)
    mesh = make_mesh((1, 8), ("data", "model"))
    base, shard = _run_pair(cfg, params,
                            _prompts(cfg, [7, 19, 12, 30, 5, 23]),
                            mesh, max_slots=3, max_len=48)
    _assert_identical(base, shard)


@pytest.mark.slow
def test_dense_sharded_pool_rows_are_head_sharded():
    """The slot pool's KV rows live 1/TP-per-device: each addressable shard
    holds Hkv/TP heads, so per-chip KV HBM drops ~1/TP."""
    _need_devices()
    cfg = _cfg()
    params = _params(cfg)
    mesh = make_mesh((1, 8), ("data", "model"))
    eng = ContinuousBatchingEngine(cfg, params, max_slots=3, max_len=48,
                                   mesh=mesh)
    specs = jax.tree_util.tree_leaves(eng._pool_sh)
    assert specs, "no pool shardings built"
    k_sh = eng._pool_sh["stage0"]["pos0"]["k"]
    assert "model" in _axes(k_sh.spec)
    # materialize the pool exactly as run() does and check shard shapes
    from repro.serve.engine import init_pool
    pool = jax.device_put(init_pool(cfg, 3, 48), eng._pool_sh)
    leaf = pool["stage0"]["pos0"]["k"]          # [slots, T, Hkv, dh]
    shard = leaf.addressable_shards[0].data
    assert shard.shape[-2] == cfg.num_kv_heads // 8
    assert shard.size == leaf.size // 8


@pytest.mark.slow
def test_dense_sharded_identity_bhtd_data_axis():
    """Head-major pool layout on a (data=4, model=2) mesh: batch over the
    data axis, heads over model — the full production-mesh shape."""
    _need_devices()
    cfg = _cfg(kv_cache_layout="bhtd")
    params = _params(cfg)
    mesh = make_mesh((4, 2), ("data", "model"))
    base, shard = _run_pair(cfg, params, _prompts(cfg, [9, 17, 26, 6]),
                            mesh, max_slots=4, max_len=40)
    _assert_identical(base, shard)


@pytest.mark.slow
def test_chunked_prefill_sharded_identity():
    _need_devices()
    cfg = _cfg()
    params = _params(cfg)
    mesh = make_mesh((1, 8), ("data", "model"))
    base, shard = _run_pair(cfg, params, _prompts(cfg, [21, 9, 14, 6]),
                            mesh, max_slots=2, max_len=40, prefill_chunk=8)
    _assert_identical(base, shard)
    assert shard[1]["stats"].prefill_chunks > len(
        shard[1]["results"])               # chunking actually engaged


@pytest.mark.slow
def test_paged_sharded_identity():
    _need_devices()
    cfg = _cfg()
    params = _params(cfg)
    mesh = make_mesh((1, 8), ("data", "model"))
    base, shard = _run_pair(cfg, params, _prompts(cfg, [9, 21, 14, 6],
                                                  seed=1),
                            mesh, max_slots=2, max_len=40,
                            kv_mode="paged", page_size=8)
    _assert_identical(base, shard)
    eng, out = shard
    # page pools are head-sharded; entry metadata replicated
    assert "model" in _axes(eng._store_sh["k_pages"].spec)
    assert not _axes(eng._store_sh["pos_pages"].spec)
    assert out["stats"].kv_entries_saved_fraction == \
        base[1]["stats"].kv_entries_saved_fraction


@pytest.mark.slow
def test_paged_sharded_identity_under_forced_preemption():
    """A page pool too small for both residents forces mid-decode
    preemption; the sharded engine preempts at the same step and re-decodes
    to identical tokens (the allocator is host-side and never sharded)."""
    _need_devices()
    cfg = _cfg()
    params = _params(cfg)
    mesh = make_mesh((1, 8), ("data", "model"))
    base, shard = _run_pair(cfg, params, _prompts(cfg, [8, 8], seed=1),
                            mesh, max_new=16, max_slots=2, max_len=48,
                            kv_mode="paged", page_size=8, num_pages=6)
    _assert_identical(base, shard)
    assert shard[1]["stats"].preemptions >= 1


@pytest.mark.slow
def test_paged_chunked_sharded_identity():
    _need_devices()
    cfg = _cfg()
    params = _params(cfg)
    mesh = make_mesh((1, 8), ("data", "model"))
    base, shard = _run_pair(cfg, params, _prompts(cfg, [21, 9, 14, 6],
                                                  seed=1),
                            mesh, max_slots=2, max_len=40,
                            kv_mode="paged", page_size=8, prefill_chunk=8)
    _assert_identical(base, shard)


@pytest.mark.slow
@pytest.mark.parametrize("kv_mode", ["dense", "paged"])
def test_fused_decode_loop_sharded_identity(kv_mode):
    """The device-resident N-step epoch (``decode_steps > 1``) under TP8:
    the scan's carry (feed/t/active masks) stays replicated while cache
    and params ride their serve-mode shardings — tokens must match the
    *unsharded single-step* engine bit for bit, with one jitted dispatch
    per epoch on both sides of the mesh boundary."""
    _need_devices()
    cfg = _cfg()
    params = _params(cfg)
    mesh = make_mesh((1, 8), ("data", "model"))
    prompts = _prompts(cfg, [7, 19, 12, 30, 5])
    kw = dict(max_slots=3, max_len=48, kv_mode=kv_mode)
    base = ContinuousBatchingEngine(cfg, params, **kw)
    for p in prompts:
        base.submit(p, max_new_tokens=10)
    ob = base.run()
    shard = ContinuousBatchingEngine(cfg, params, mesh=mesh,
                                     decode_steps=8, **kw)
    for p in prompts:
        shard.submit(p, max_new_tokens=10)
    os_ = shard.run()
    for uid in ob["results"]:
        np.testing.assert_array_equal(ob["results"][uid].tokens,
                                      os_["results"][uid].tokens)
        assert ob["results"][uid].finish_reason == \
            os_["results"][uid].finish_reason
    assert os_["stats"].decode_dispatches < ob["stats"].decode_dispatches


@pytest.mark.slow
def test_sharded_rejects_bad_policy_mode():
    _need_devices(2)
    from repro.distributed.sharding import ShardingPolicy
    cfg = _cfg()
    params = _params(cfg)
    mesh = make_mesh((1, 2), ("data", "model"))
    pol = ShardingPolicy(mesh, cfg, mode="train")
    with pytest.raises(ValueError, match="serve-mode"):
        ContinuousBatchingEngine(cfg, params, mesh=mesh,
                                 sharding_policy=pol)
