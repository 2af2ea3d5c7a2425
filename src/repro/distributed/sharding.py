"""Sharding policy: how every parameter, activation, and cache tensor maps
onto the production mesh.

Axes:
  data  — batch / FSDP axis (16-way per pod)
  model — tensor/expert/sequence-parallel axis (16-way)
  pod   — optional pod axis (2-way): batch (and FSDP for the largest models)

Model code stays mesh-agnostic: it calls ``hint(x, name)`` at key points,
which applies ``with_sharding_constraint`` when a policy is active and is a
no-op otherwise (CPU tests).  Parameter specs are resolved from pytree paths
by ``param_specs`` — the same rules serve pjit in_shardings and checkpoint
resharding.
"""
from __future__ import annotations

import contextlib
import re
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig

# Parameters above this count get FSDP over (pod, data) instead of data only.
_POD_FSDP_PARAM_THRESHOLD = 60e9


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


_SERVE_HBM_BUDGET = 12e9   # per-chip bytes before serve mode re-shards weights


def _fitted_spec(mesh, shape, spec) -> P:
    """Drop spec axes whose mesh-axis product doesn't divide their dim
    (jit in/out shardings require exact divisibility; the surviving axes
    still pin the layout — param_specs, cache_specs and hint all share
    this partial-fit rule so constraints never fight each other)."""
    fixed = []
    for dim, ax in zip(shape, spec):
        if ax is not None:
            size = 1
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                size *= mesh.shape[a]
            if not size or dim % size != 0:
                ax = None
        fixed.append(ax)
    return P(*fixed)


@dataclass
class ShardingPolicy:
    mesh: Mesh
    cfg: ModelConfig
    mode: str = "train"                 # train | serve
    fsdp_over_pod: Optional[bool] = None
    # ZeRO-1: replicate params over the data axis (weight-stationary
    # training — no per-layer weight all-gathers); optimizer state stays
    # data-sharded.  The §Perf hillclimb lever for collective-bound train.
    zero1: bool = False
    # name -> PartitionSpec for activation hints
    overrides: Dict[str, P] = field(default_factory=dict)

    def __post_init__(self):
        axes = self.mesh.axis_names
        self.has_pod = "pod" in axes
        if self.fsdp_over_pod is None:
            self.fsdp_over_pod = (self.has_pod and
                                  self.cfg.param_count() > _POD_FSDP_PARAM_THRESHOLD)
        self.dp: Tuple[str, ...] = (("pod", "data") if self.has_pod else ("data",))
        self.model_size = self.mesh.shape["model"]
        if self.mode == "serve":
            # weight-stationary inference: shard weights over `model` only
            # unless they don't fit, in which case spill onto the data axis
            # (re-gathered each step — the memory-capacity trade).
            per_chip = 2 * self.cfg.param_count() / self.model_size
            if per_chip <= _SERVE_HBM_BUDGET:
                self.fsdp: Any = None
            elif not self.has_pod or per_chip / self.mesh.shape["data"] \
                    <= _SERVE_HBM_BUDGET:
                self.fsdp = "data"
            else:
                self.fsdp = ("pod", "data")
        else:
            self.fsdp = (("pod", "data") if self.fsdp_over_pod else "data")
            self.opt_fsdp = self.fsdp
            if self.zero1:
                self.fsdp = None

    # ------------------------------------------------------------ activations
    def spec(self, name: str) -> Optional[P]:
        if name in self.overrides:
            return self.overrides[name]
        dp, fsdp = self.dp, self.fsdp
        E = self.cfg.num_experts
        ep = E and E % self.model_size == 0
        train = self.mode == "train"
        table = {
            # [B, T, D]
            "activation": P(dp, None, None),
            # [B, T, K] input of a projection that contracts over K (the
            # attention output before wo, the MLP hidden before down).
            # Serve mode pins it whole: left free, the partitioner may
            # shard K over an idle mesh axis (a batch-1 prefill on
            # data > 1) and all-reduce partial sums.  Training leaves it
            # to the partitioner (Megatron row-parallel wo/down).
            "contracted": None if train else P(dp, None, None),
            # [B, T, D] inter-stage residual carry: sequence-parallel in
            # training (the per-stage saved residuals dominate HBM
            # otherwise — Megatron-SP); replicated-T at inference.
            "residual": P(dp, "model", None) if train else P(dp, None, None),
            # [B, T, V]
            "logits": P(dp, None, "model"),
            # [B, T, Hq, dh]
            "q_heads": P(dp, None, "model", None),
            # [B, T, Hkv, dh] — serve mode head-shards (matching the
            # head-sharded attention split and the serve cache_specs);
            # training replicates (kv heads usually < model size there)
            "kv_heads": (P(dp, None, None, None) if train
                         else P(dp, None, "model", None)),
            # decode-step KV cache [B, T, Hkv, dh]: sequence-parallel over
            # model in training; head-sharded at serve time (each device
            # owns Hkv/TP heads of the whole history — no cross-device
            # traffic inside the attention dot)
            "kv_cache_step": (P(dp, "model", None, None) if train
                              else P(dp, None, "model", None)),
            # head-major decode cache [B, Hkv, T, dh]
            "kv_cache_step_bhtd": (P(dp, None, "model", None) if train
                                   else P(dp, "model", None, None)),
            # prefill/train KV view [B, T, Hkv, dh]: carried across the layer
            # scan — sequence-parallel in training for the same reason;
            # head-sharded at serve time like the caches it feeds.
            "kv_view": (P(dp, "model", None, None) if train
                        else P(dp, None, "model", None)),
            # [B, T] per-token Σy² carry (incremental-reduction): follows
            # the residual it accompanies — sequence-sharded in training,
            # replicated over model at serve time (every device needs the
            # full-row norm to take identical routing/sampling decisions)
            "res_sq": P(dp, "model") if train else P(dp, None),
            # [E, C, D]
            "moe_buffer": P("model", None, None) if ep else P(None, "model", None),
            # [B, T] routing masks
            "gate": P(dp, None),
            # mamba state [B, H, P, N]
            "ssm_state": P(dp, "model", None, None),
            # conv state [B, W-1, C]
            "conv_state": P(dp, None, None),
        }
        return table.get(name)

    def named(self, name: str) -> Optional[NamedSharding]:
        s = self.spec(name)
        return NamedSharding(self.mesh, s) if s is not None else None

    # ------------------------------------------------------------- parameters
    def _param_spec(self, path: str, shape: Tuple[int, ...]) -> P:
        cfg, fsdp = self.cfg, self.fsdp
        E = cfg.num_experts
        ep = E and E % self.model_size == 0
        # --- embeddings / unembedding ---
        if path.endswith("embed/table"):
            return P("model", fsdp)
        if "lm_head" in path:
            return P(fsdp, "model")
        # --- MoE experts ---
        if re.search(r"(^|/)(w_up|w_gate)$", path):
            return P("model", fsdp, None) if ep else P(None, fsdp, "model")
        if path.endswith("w_down"):
            return P("model", None, fsdp) if ep else P(None, "model", fsdp)
        if re.search(r"moe[^/]*/gate$", path) or path.endswith("/gate") and len(shape) == 2 \
                and shape[-1] == E:
            return P(fsdp, None)
        # --- routers (tiny) ---
        if "router" in path:
            return P(None, None)
        # --- attention ---
        if path.endswith("wqkv/w"):
            # merged [q|k|v]: the column split is (ai, ki, ki) — shard the
            # output dim only when every slice divides the axis cleanly
            if (cfg.attn_inner_dim % self.model_size == 0
                    and cfg.kv_inner_dim % self.model_size == 0
                    and cfg.num_kv_heads >= self.model_size):
                return P(fsdp, "model")
            # GQA fallback (kv heads < model axis): the q/k/v boundaries
            # can't split column-wise, so go row-parallel over the input
            # dim — memory-balanced (1/model_size per device) instead of
            # replicating the large q projection with the legacy split
            # layout's column rules.
            row = ((fsdp if isinstance(fsdp, tuple) else (fsdp,))
                   if fsdp else ()) + ("model",)
            return P(row, None)
        if path.endswith("wq/w"):                          # legacy split
            return P(fsdp, "model")
        if path.endswith(("wk/w", "wv/w")):
            # kv_inner usually < model size heads; shard when divisible
            if shape[-1] % self.model_size == 0 and cfg.num_kv_heads >= self.model_size:
                return P(fsdp, "model")
            return P(fsdp, None)
        if path.endswith("wo/w"):
            # training: Megatron row-parallel (input dim over model — one
            # psum per block).  serve: column split over the *output* dim —
            # the head-sharded attention output is all-gathered instead,
            # so no cross-device reduction reorders fp sums (all-gathers
            # move the same bytes as the psum at decode M).  A shard's
            # narrower matmul may still round differently from one
            # device's: sharded serving matches it to rounding
            # (docs/distributed.md).
            return (P("model", fsdp) if self.mode == "train"
                    else P(fsdp, "model"))
        # --- MLP ---
        if path.endswith(("gu/w", "up/w", "gate/w")):
            return P(fsdp, "model")
        if path.endswith("down/w"):
            # row-parallel in training, column split at serve time — same
            # rationale as wo/w above.
            return (P("model", fsdp) if self.mode == "train"
                    else P(fsdp, "model"))
        # --- SSM ---
        if re.search(r"in_proj_(z|x)/w$", path):
            return P(fsdp, "model")
        if re.search(r"in_proj_(bc|dt)/w$", path):
            return P(fsdp, None)
        if path.endswith("out_proj/w"):
            # same train-row / serve-column split as wo/w and down/w: a
            # Mamba block's output projection must not psum at serve time
            # either.
            return (P("model", fsdp) if self.mode == "train"
                    else P(fsdp, "model"))
        if path.endswith("conv_x_w"):
            return P(None, "model")
        # --- quantized variants: w_int/scale share the dense layout ---
        if path.endswith(("w_int", "scale")):
            base = path.rsplit("/", 1)[0] + "/w"
            return self._param_spec(base, shape)
        # --- norms, biases, scalars: replicate ---
        return P(*([None] * len(shape)))

    def param_specs(self, tree) -> Any:
        """tree: params pytree (arrays or ShapeDtypeStructs) -> NamedSharding tree."""
        def one(path, leaf):
            ps = _path_str(path)
            shape = leaf.shape
            stacked = "stages/" in ps or ps.startswith("stages")
            if stacked:
                shape = shape[1:]                 # scan-stacked leading dim
            spec = list(self._param_spec(ps, shape))
            if stacked:
                spec = [None] + spec
                shape = leaf.shape
            return NamedSharding(self.mesh,
                                 _fitted_spec(self.mesh, shape, spec))

        return jax.tree_util.tree_map_with_path(one, tree)

    def opt_state_specs(self, opt_shapes) -> Any:
        """AdamW moments mirror the param specs; the count is replicated.
        Under ZeRO-1 the moments keep their data-axis shard even though the
        params are replicated."""
        saved = self.fsdp
        if self.zero1 and self.mode == "train":
            self.fsdp = self.opt_fsdp
        try:
            m = self.param_specs(opt_shapes["m"])
            v = self.param_specs(opt_shapes["v"])
        finally:
            self.fsdp = saved
        return {"m": m, "v": v,
                "count": NamedSharding(self.mesh, P())}

    # ------------------------------------------------------------------ cache
    def cache_specs(self, cache_tree, seq_shard: bool = False,
                    layout: str = "bthd", seq_fallback: bool = True) -> Any:
        """Decode-cache sharding — covers the lock-step decode caches, the
        continuous-batching engine's slot pool (``serve/engine.init_pool``:
        the same tree with ``max_slots`` rows) and the paged ``KVStore``
        (``kvcache/paged.init_store``: the flat ``*_pages`` dict).

        ``mode == "serve"`` head-shards KV over ``model`` (each device owns
        ``Hkv/TP`` heads of every slot's whole history — the split matching
        head-sharded attention, so the decode dot is cross-device-silent and
        per-chip KV HBM drops ~1/TP); when the head count doesn't divide
        the model axis (GQA below TP) it falls back to the sequence split
        so per-chip KV still stays ~1/TP instead of replicating; training
        always uses the sequence-parallel split.  ``seq_fallback=False``
        replicates instead on non-dividing heads — for *transient*
        single-request prefill/staging caches, whose bucketed time axes
        have no fixed length a sequence split could be guaranteed to
        divide (the long-lived pool/store is what per-chip HBM rides on).  Entry metadata (``pos/l0/l1`` pages) is replicated: block
        tables, free list and history indirection stay host-global so the
        scheduler and ``PageAllocator`` are unchanged under TP.
        seq_shard=True (long_500k, batch too small to shard) puts the
        KV/conv sequence axis on the mesh instead."""
        dp = self.dp
        serve = self.mode == "serve" and not seq_shard

        def one(path, leaf):
            name = _path_str(path).rsplit("/", 1)[-1]
            nd = leaf.ndim
            if name in ("k_pages", "v_pages"):
                # paged entry stream [P, page, Hkv, dh]: shard the head
                # axis; page geometry stays device-uniform so one global
                # block table addresses every shard.  GQA fallback (heads
                # don't divide TP): shard the page axis instead — reads
                # gather cross-device, but per-chip store memory stays
                # 1/TP rather than silently replicating.
                if leaf.shape[2] % self.model_size == 0:
                    spec = (None, None, "model", None)
                else:
                    spec = ("model", None, None, None)
            elif name in ("k_scales", "v_scales"):
                # quantized-page scales [P, page, Hkv]: ride the payload's
                # sharding so code and scale for an entry-head pair stay
                # on the same chip
                if leaf.shape[2] % self.model_size == 0:
                    spec = (None, None, "model")
                else:
                    spec = ("model", None, None)
            elif name in ("pos_pages", "l0_pages", "l1_pages"):
                spec = (None,) * nd                   # replicated metadata
            elif name in ("k", "v"):
                lead = (None,) * (nd - 4)
                seq_axes = (("data", "model") if not self.has_pod
                            else ("pod", "data", "model"))
                bhtd = (layout == "bhtd"
                        and leaf.shape[nd - 2] > leaf.shape[nd - 3])
                heads = leaf.shape[nd - 3] if bhtd else leaf.shape[nd - 2]
                if serve and heads % self.model_size == 0:
                    # [..., B, Hkv, T, dh] / [..., B, T, Hkv, dh]
                    spec = lead + ((dp, "model", None, None) if bhtd
                                   else (dp, None, "model", None))
                elif serve and seq_fallback:
                    # GQA fallback (Hkv < TP or non-dividing): keep the
                    # sequence split — per-chip KV stays ~1/TP instead of
                    # replicating (bit-identity is then fp-tolerance only,
                    # like the row-parallel wqkv fallback it accompanies)
                    spec = lead + ((dp, None, "model", None) if bhtd
                                   else (dp, "model", None, None))
                elif serve:
                    # transient cache with non-dividing heads: replicate
                    # (its bucketed time extents can't carry a guaranteed
                    # divisible sequence split)
                    spec = lead + (dp, None, None, None)
                elif bhtd:
                    # [..., B, Hkv, T, dh] (local ring caches stay bthd)
                    spec = lead + ((None, None, seq_axes, None) if seq_shard
                                   else (dp, None, "model", None))
                elif seq_shard:
                    spec = lead + (None, seq_axes, None, None)
                else:
                    spec = lead + (dp, "model", None, None)
            elif name == "ssm":
                # [..., B, H, P, N]
                lead = (None,) * (nd - 4)
                spec = lead + (None if seq_shard else dp, "model", None, None)
            elif name == "conv_x":
                lead = (None,) * (nd - 3)
                spec = lead + (None if seq_shard else dp, None, "model")
            elif name == "conv_bc":
                lead = (None,) * (nd - 3)
                spec = lead + (None if seq_shard else dp, None, None)
            else:
                spec = (None,) * nd
            return NamedSharding(self.mesh,
                                 _fitted_spec(self.mesh, leaf.shape, spec))

        return jax.tree_util.tree_map_with_path(one, cache_tree)


# ---------------------------------------------------------------------------
# Active-policy plumbing (model code calls ``hint``)
# ---------------------------------------------------------------------------
_ACTIVE: Optional[ShardingPolicy] = None


@contextlib.contextmanager
def set_policy(policy: Optional[ShardingPolicy]):
    global _ACTIVE
    prev, _ACTIVE = _ACTIVE, policy
    try:
        yield policy
    finally:
        _ACTIVE = prev


def active_policy() -> Optional[ShardingPolicy]:
    return _ACTIVE


def hint(x: jnp.ndarray, name: str) -> jnp.ndarray:
    """Apply the active policy's sharding constraint for ``name`` (no-op
    when no policy is active or the tensor rank doesn't match the rule).
    Axes that don't divide their mesh product are dropped from the spec
    (not the whole constraint): a batch-1 prefill on a data>1 mesh keeps
    its replicated-over-model pins — losing them entirely lets GSPMD pick
    divergent layouts — while e.g. GQA KV heads below the serve TP degree
    just stay replicated at the hint site (the cache in/out shardings
    carry the sequence-split fallback)."""
    pol = _ACTIVE
    if pol is None:
        return x
    spec = pol.spec(name)
    if spec is None or len(spec) != x.ndim:
        return x
    fitted = _fitted_spec(pol.mesh, x.shape, spec)
    return jax.lax.with_sharding_constraint(x, NamedSharding(pol.mesh,
                                                             fitted))
