"""ShapeDtypeStruct input specs + step-function builders for every
(arch × shape × mesh) cell.  Pure AOT: nothing here allocates device memory
— params/optimizer/cache shapes come from ``jax.eval_shape`` and the dry-run
lowers against the structs (the shannon/kernels pattern).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES, ModelConfig
from repro.distributed.sharding import ShardingPolicy, set_policy
from repro.models import model as model_lib
from repro.optim import adamw_init, adamw_update, apply_updates, cosine_schedule


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def batch_specs(cfg: ModelConfig, batch: int, seq: int) -> Dict[str, Any]:
    """Model-input ShapeDtypeStructs for one (global_batch, seq)."""
    if cfg.frontend == "token":
        d: Dict[str, Any] = {"tokens": _sds((batch, seq), jnp.int32)}
    else:
        # modality frontend is a stub: precomputed frame/patch embeddings
        d = {"embeds": _sds((batch, seq), cfg.dtype)}
        d["embeds"] = _sds((batch, seq, cfg.d_model), cfg.dtype)
    if cfg.pos_embedding == "mrope":
        d["positions"] = _sds((3, batch, seq), jnp.int32)
    return d


def train_batch_specs(cfg: ModelConfig, batch: int, seq: int) -> Dict[str, Any]:
    d = batch_specs(cfg, batch, seq)
    d["labels"] = _sds((batch, seq), jnp.int32)
    return d


def input_specs(cfg: ModelConfig, shape_name: str) -> Dict[str, Any]:
    s = SHAPES[shape_name]
    if s["kind"] == "train":
        return train_batch_specs(cfg, s["global_batch"], s["seq_len"])
    if s["kind"] == "prefill":
        return batch_specs(cfg, s["global_batch"], s["seq_len"])
    return batch_specs(cfg, s["global_batch"], 1)           # decode


def _logits_sharding(cfg: ModelConfig, policy: ShardingPolicy,
                     batch: int) -> NamedSharding:
    dpsz = 1
    for a in policy.dp:
        dpsz *= policy.mesh.shape[a]
    b_ax = policy.dp if batch % dpsz == 0 else None
    v_ax = "model" if cfg.vocab_size % policy.mesh.shape["model"] == 0 else None
    return NamedSharding(policy.mesh, P(b_ax, v_ax))


def _batch_shardings(batch_tree, policy: ShardingPolicy):
    mesh, dp = policy.mesh, policy.dp

    def one(path, leaf):
        name = str(path[-1].key)
        B = leaf.shape[0] if name != "positions" else leaf.shape[1]
        dpsz = 1
        for a in dp:
            dpsz *= mesh.shape[a]
        ax = dp if B % dpsz == 0 else None
        if name == "positions":
            return NamedSharding(mesh, P(None, ax, None))
        spec = (ax,) + (None,) * (leaf.ndim - 1)
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(one, batch_tree)


# ---------------------------------------------------------------------------
# Step builders: return (fn, example_args, in_shardings, out_shardings,
# donate_argnums)
# ---------------------------------------------------------------------------

def serve_cfg(cfg: ModelConfig) -> ModelConfig:
    """Inference-time configuration: gather (compacted) execution for the
    prefill pass — the SkipOPU selective-execution pipeline."""
    return dataclasses.replace(
        cfg, skip=dataclasses.replace(cfg.skip, mode="gather"), remat=False)


def build_train_step(cfg: ModelConfig, policy: ShardingPolicy,
                     shape_name: str, lr: float = 3e-4,
                     microbatches: Optional[int] = None):
    s = SHAPES[shape_name]
    n_params = cfg.param_count()
    # ≥200B: bf16 momentum + factored second moment (see optim/adamw.py)
    lowmem = n_params > 2e11
    if microbatches is None:
        microbatches = 32 if lowmem else (16 if n_params > 3e10 else 8)
    acc_dtype = jnp.bfloat16 if lowmem else jnp.float32
    batch_tree = train_batch_specs(cfg, s["global_batch"], s["seq_len"])
    params_shapes = jax.eval_shape(partial(model_lib.init_params, cfg=cfg),
                                   jax.random.PRNGKey(0))
    opt_shapes = jax.eval_shape(partial(adamw_init, lowmem=lowmem),
                                params_shapes)
    rng_shape = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    schedule = cosine_schedule(lr, 100, 10_000)
    B = s["global_batch"]
    mb = microbatches if B % microbatches == 0 else 1

    def split_mb(batch):
        def one(path, leaf):
            name = str(path[-1].key)
            if name == "positions":                 # [3, B, T]
                return leaf.reshape(leaf.shape[0], mb, B // mb,
                                    *leaf.shape[2:]).swapaxes(0, 1)
            return leaf.reshape(mb, B // mb, *leaf.shape[1:])
        return jax.tree_util.tree_map_with_path(one, batch)

    def train_step(params, opt_state, batch, rng):
        with set_policy(policy):
            grad_fn = jax.value_and_grad(model_lib.train_loss, has_aux=True)
            if mb == 1:
                (loss, metrics), grads = grad_fn(params, batch, rng, cfg)
            else:
                # gradient accumulation: bounds activation memory to one
                # microbatch (the per-device global batch doesn't fit HBM
                # at train_4k otherwise)
                mb_batch = split_mb(batch)
                acc0 = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, acc_dtype), params)
                if policy.zero1:
                    # ZeRO-2: keep the accumulator data-sharded so each
                    # microbatch's gradient reduction lowers to a
                    # reduce-scatter (half the all-reduce bytes); the
                    # updated params all-gather once per step.
                    saved = policy.fsdp
                    policy.fsdp = policy.opt_fsdp
                    try:
                        acc_specs = policy.param_specs(params)
                    finally:
                        policy.fsdp = saved
                    acc0 = jax.tree_util.tree_map(
                        jax.lax.with_sharding_constraint, acc0, acc_specs)

                def body(carry, xs):
                    acc, k = carry
                    bslice, i = xs
                    (loss, metrics), g = grad_fn(
                        params, bslice, jax.random.fold_in(k, i), cfg)
                    acc = jax.tree_util.tree_map(
                        lambda a, gi: a + (gi / mb).astype(acc_dtype),
                        acc, g)
                    return (acc, k), (loss, metrics)

                (grads, _), (losses, metricses) = jax.lax.scan(
                    body, (acc0, rng), (mb_batch, jnp.arange(mb)))
                metrics = jax.tree_util.tree_map(
                    lambda m: m.mean(), metricses)
            updates, opt_state = adamw_update(grads, opt_state, params,
                                              schedule)
            params = apply_updates(params, updates)
        return params, opt_state, metrics

    p_sh = policy.param_specs(params_shapes)
    o_sh = policy.opt_state_specs(opt_shapes)
    rep = NamedSharding(policy.mesh, P())
    in_sh = (p_sh, o_sh, _batch_shardings(batch_tree, policy), rep)
    out_sh = (p_sh, o_sh,
              jax.tree_util.tree_map(lambda _: rep,
                                     {"loss": 0, "xent": 0, "router_loss": 0,
                                      "moe_lb_loss": 0, "keep_frac": 0}))
    args = (params_shapes, opt_shapes, batch_tree, rng_shape)
    return train_step, args, in_sh, out_sh, (0, 1)


def _param_shapes(cfg: ModelConfig):
    """Parameter ShapeDtypeStructs — int4-coded when cfg.quant.enabled
    (the paper's W4 deployment: the dry-run lowers against the quantized
    tree so weight HBM/collective bytes reflect int4 storage)."""
    return jax.eval_shape(
        partial(model_lib.init_params, cfg=cfg, quantize=cfg.quant.enabled),
        jax.random.PRNGKey(0))


def build_prefill_step(cfg: ModelConfig, policy: ShardingPolicy,
                       shape_name: str):
    cfg = serve_cfg(cfg)
    s = SHAPES[shape_name]
    batch_tree = batch_specs(cfg, s["global_batch"], s["seq_len"])
    params_shapes = _param_shapes(cfg)

    def prefill_step(params, batch):
        with set_policy(policy):
            logits, cache, stats = model_lib.prefill(params, batch, cfg)
        return logits, cache, {"keep": stats["keep_frac_sum"]}

    in_sh = (policy.param_specs(params_shapes),
             _batch_shardings(batch_tree, policy))
    args = (params_shapes, batch_tree)
    cache_shapes = jax.eval_shape(lambda p, b: prefill_step(p, b)[1],
                                  params_shapes, batch_tree)
    rep = NamedSharding(policy.mesh, P())
    out_sh = (_logits_sharding(cfg, policy, s["global_batch"]),
              policy.cache_specs(cache_shapes),
              {"keep": rep})
    return prefill_step, args, in_sh, out_sh, ()


def build_serve_step(cfg: ModelConfig, policy: ShardingPolicy,
                     shape_name: str):
    """decode_* / long_*: one new token against a seq_len-deep KV cache."""
    cfg = serve_cfg(cfg)
    s = SHAPES[shape_name]
    B, T = s["global_batch"], s["seq_len"]
    batch_tree = batch_specs(cfg, B, 1)
    params_shapes = _param_shapes(cfg)
    cache_shapes = jax.eval_shape(
        partial(model_lib.init_decode_cache, cfg, B, T))
    seq_shard = shape_name.startswith("long")
    if seq_shard:
        # serve-mode hints now default to head-sharded KV (the continuous
        # engine's split); the long shapes keep the sequence split the
        # seq_shard cache_specs build, so pin the in-step hints to match.
        seq = (("pod", "data", "model") if policy.has_pod
               else ("data", "model"))
        policy.overrides.setdefault("kv_cache_step", P(None, seq, None, None))
        policy.overrides.setdefault("kv_cache_step_bhtd",
                                    P(None, None, seq, None))
        policy.overrides.setdefault("kv_heads", P(None, None, None, None))
        policy.overrides.setdefault("kv_view", P(None, None, None, None))

    def serve_step(params, cache, batch, t):
        with set_policy(policy):
            logits, cache, stats = model_lib.decode_step(params, cache,
                                                         batch, t, cfg)
        return logits, cache, {"keep": stats["keep_frac_sum"]}

    cache_sh = policy.cache_specs(cache_shapes, seq_shard=seq_shard,
                                  layout=cfg.kv_cache_layout)
    rep = NamedSharding(policy.mesh, P())
    in_sh = (policy.param_specs(params_shapes), cache_sh,
             _batch_shardings(batch_tree, policy), rep)
    out_sh = (_logits_sharding(cfg, policy, B), cache_sh, {"keep": rep})
    args = (params_shapes, cache_shapes, batch_tree,
            _sds((), jnp.int32))
    return serve_step, args, in_sh, out_sh, (1,)


def build_step(cfg: ModelConfig, policy: ShardingPolicy, shape_name: str):
    kind = SHAPES[shape_name]["kind"]
    if kind == "train":
        return build_train_step(cfg, policy, shape_name)
    if kind == "prefill":
        return build_prefill_step(cfg, policy, shape_name)
    return build_serve_step(cfg, policy, shape_name)
