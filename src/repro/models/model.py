"""LanguageModel: init / train loss / prefill / decode-step over the
heterogeneous layer stack, with the SkipGPT routing + KV-reuse pipeline
threaded through every layer.

Public entry points (all pure functions of (cfg, params, ...)):
  init_params            — parameter pytree
  train_loss             — chunked-softmax LM loss + router/MoE aux losses
  prefill                — forward pass that builds the per-layer KV caches
  decode_step            — one-token autoregressive step over those caches
  init_decode_cache      — zero caches for decode-only lowering (dry-run)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, LOCAL, MAMBA, ModelConfig
from repro.distributed.sharding import hint
from repro.models import layers, ssm as ssm_mod, transformer
from repro.models.layers import Params


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, donate_argnums=0)
def _set_stage(stack: Params, i, stage: Params) -> Params:
    """``stack[i] = stage`` leaf by leaf, in place (the stack is donated)."""
    return jax.tree_util.tree_map(
        lambda b, x: jax.lax.dynamic_update_index_in_dim(b, x, i, 0),
        stack, stage)


def init_params(key, cfg: ModelConfig, quantize: bool = False) -> Params:
    """Seeded parameter pytree; with ``quantize`` every large linear weight
    is int4-coded (``quant.quantize_params`` with ``cfg.quant``'s group
    size and scale rule).

    Built one stage at a time: each stage is drawn (fp32 draw, then cast)
    and quantized on its own and written into the preallocated
    ``[S-1, ...]`` stack by a jitted update that donates the stack, so
    the live peak is the final tree plus one stage's temporaries — never
    an fp32 or pre-quantization copy of the whole stack (at llama2-7b's
    widths that copy alone is 11 GB).  The values equal
    ``transformer.stack_init``'s ``vmap(stage_init)`` over the same
    per-stage keys."""
    from repro.quant import quantize_params

    def post(p):
        if not quantize:
            return p
        return quantize_params(p, cfg.quant.group_size,
                               cfg.quant.pow2_scales)

    ks = jax.random.split(key, 4)
    sk = jax.random.split(ks[1], cfg.num_stages)
    stack: Params = {"stage0": post(transformer.stage_init(sk[0], cfg))}
    for i in range(1, cfg.num_stages):
        stage = post(transformer.stage_init(sk[i], cfg))
        if i == 1:
            stack["stages"] = jax.tree_util.tree_map(
                lambda x: jnp.zeros((cfg.num_stages - 1,) + x.shape,
                                    x.dtype), stage)
        stack["stages"] = _set_stage(stack["stages"], i - 1, stage)
    p: Params = {
        "embed": layers.embedding_init(ks[0], cfg),
        "stack": stack,
        "final_norm": layers.norm_init(cfg.d_model, cfg),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = post(layers.linear_init(ks[2], cfg.d_model,
                                               cfg.vocab_size, cfg,
                                               scale=0.02))
    return p


# ---------------------------------------------------------------------------
# Input plumbing
# ---------------------------------------------------------------------------

def _positions(batch: Dict[str, jnp.ndarray], B: int, T: int,
               cfg: ModelConfig) -> jnp.ndarray:
    if "positions" in batch:
        return batch["positions"]
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    if cfg.pos_embedding == "mrope":
        return jnp.broadcast_to(pos[None], (3, B, T))
    return pos


def _embed_inputs(params: Params, batch: Dict[str, jnp.ndarray],
                  positions: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    if cfg.frontend == "token":
        x = layers.embed(params["embed"], batch["tokens"])
    else:
        # audio/vlm stub: the modality frontend is out of scope (paper
        # backbone only); precomputed frame/patch embeddings come in.
        x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
    if cfg.pos_embedding == "sinusoidal":
        pos = positions if positions.ndim == 2 else positions[0]
        x = x + layers.sinusoidal_positions(pos, cfg.d_model).astype(x.dtype)
    return hint(x, "activation")


# ---------------------------------------------------------------------------
# Stack application
# ---------------------------------------------------------------------------

def _apply_stack(params: Params, x: jnp.ndarray, positions: jnp.ndarray,
                 cfg: ModelConfig, rng: Optional[jax.Array], train: bool,
                 collect_cache: bool
                 ) -> Tuple[jnp.ndarray, Dict, Optional[Dict],
                            Optional[jnp.ndarray]]:
    """Returns (x, stats, cache, carried_sq) — the trailing element is the
    fused pipeline's incremental-reduction carry of the final residual
    stream (mean-square per token; feeds the final norm for free)."""
    stack = params["stack"]
    S = cfg.num_stages
    r0 = jax.random.fold_in(rng, 0) if rng is not None else None

    def stage0_fn(sp, x):
        x = hint(x, "residual")
        return transformer.stage_forward(
            sp, x, None, positions, cfg, r0, train, collect_cache, True)

    if cfg.remat:
        stage0_fn = jax.checkpoint(stage0_fn)
    x, view, stats, cache0, sq = stage0_fn(stack["stage0"], x)
    gates = stats.pop("attn_gate", None)    # [nA_stage, B, T] or None
    cache: Optional[Dict] = {"stage0": cache0} if collect_cache else None

    if S > 1:
        keys = (jax.random.split(jax.random.fold_in(rng, 1), S - 1)
                if rng is not None else None)

        def body(carry, xs):
            x, view, sq = carry
            x = hint(x, "residual")
            if view is not None:
                view = (hint(view[0], "kv_view"), hint(view[1], "kv_view"))
            if keys is not None:
                sp, k = xs
            else:
                sp, k = xs, None
            x, view, s, c, sq = transformer.stage_forward(
                sp, x, view, positions, cfg, k, train, collect_cache, False,
                carried_sq=sq)
            g = s.pop("attn_gate", None)
            if view is not None:
                view = (hint(view[0], "kv_view"), hint(view[1], "kv_view"))
            return (hint(x, "residual"), view, sq), (s, c, g)

        if cfg.remat:
            body = jax.checkpoint(body)
        if cfg.scan_layers:
            xs = (stack["stages"], keys) if keys is not None else stack["stages"]
            (x, view, sq), (s_scan, c_scan, g_scan) = jax.lax.scan(
                body, (x, view, sq), xs)
            stats = jax.tree_util.tree_map(lambda a, b: a + b.sum(axis=0),
                                           stats, s_scan)
            if collect_cache:
                cache["stages"] = c_scan
            if gates is not None:
                gates = jnp.concatenate([gates[None], g_scan], axis=0)
        else:
            # unrolled (dry-run accounting mode: XLA cost_analysis does not
            # multiply while-loop bodies by trip count)
            c_list, g_list = [], []
            for i in range(S - 1):
                sp = jax.tree_util.tree_map(lambda l: l[i], stack["stages"])
                xs = (sp, keys[i]) if keys is not None else sp
                (x, view, sq), (s, c, g) = body((x, view, sq), xs)
                stats = jax.tree_util.tree_map(lambda a, b: a + b, stats, s)
                c_list.append(c)
                g_list.append(g)
            if collect_cache:
                cache["stages"] = jax.tree_util.tree_map(
                    lambda *ls: jnp.stack(ls), *c_list)
            if gates is not None:
                gates = jnp.concatenate(
                    [gates[None]] + [g[None] for g in g_list], axis=0)
        if gates is not None:
            # [S, nA_stage, B, T] -> [L_attn, B, T] in stack order
            gates = gates.reshape((-1,) + gates.shape[-2:])
    if gates is not None:
        stats["attn_gate"] = gates
    return x, stats, cache, sq


# ---------------------------------------------------------------------------
# Training loss (chunked softmax cross-entropy)
# ---------------------------------------------------------------------------

def _xent_chunk(x: jnp.ndarray, labels: jnp.ndarray, weights: jnp.ndarray,
                params: Params, cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [B, Tc, D] -> (sum nll, sum weight).  Bounds peak logits memory to
    one sequence chunk (important for the 262k-vocab archs)."""
    logits = layers.unembed(params["embed"], params.get("lm_head"), x, cfg)
    logits = hint(logits, "logits").astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (lse - ll) * weights
    return nll.sum(), weights.sum()


def chunked_xent(x: jnp.ndarray, labels: jnp.ndarray, weights: jnp.ndarray,
                 params: Params, cfg: ModelConfig) -> jnp.ndarray:
    B, T, D = x.shape
    C = min(cfg.xent_chunk, T)
    if T % C:
        C = T
    nc = T // C
    if nc == 1:
        nll, w = _xent_chunk(x, labels, weights, params, cfg)
        return nll / jnp.maximum(w, 1.0)

    def chunk_fn(xc, lc, wc, params):
        return _xent_chunk(xc, lc, wc, params, cfg)

    if cfg.remat:
        chunk_fn = jax.checkpoint(chunk_fn)

    def body(carry, inp):
        xc, lc, wc = inp
        nll, w = chunk_fn(xc, lc, wc, params)
        return (carry[0] + nll, carry[1] + w), None

    xs = (x.reshape(B, nc, C, D).swapaxes(0, 1),
          labels.reshape(B, nc, C).swapaxes(0, 1),
          weights.reshape(B, nc, C).swapaxes(0, 1))
    (nll, w), _ = jax.lax.scan(body, (jnp.float32(0.0), jnp.float32(0.0)), xs)
    return nll / jnp.maximum(w, 1.0)


def train_loss(params: Params, batch: Dict[str, jnp.ndarray],
               rng: Optional[jax.Array], cfg: ModelConfig
               ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    if cfg.frontend == "token":
        B, T = batch["tokens"].shape
    else:
        B, T = batch["embeds"].shape[:2]
    positions = _positions(batch, B, T, cfg)
    x = _embed_inputs(params, batch, positions, cfg)
    x, stats, _, sq = _apply_stack(params, x, positions, cfg, rng, True, False)
    x = layers.norm_apply(params["final_norm"], x, cfg, stats=sq)

    labels = batch["labels"]
    weights = batch.get("loss_weights",
                        jnp.ones(labels.shape, jnp.float32))
    xent = chunked_xent(x, labels, weights, params, cfg)

    router_loss = stats["router_loss"]
    moe_lb = stats["moe_lb_loss"]
    loss = (xent + cfg.skip.router_loss_weight * router_loss
            + cfg.moe_lb_weight * moe_lb)
    keep = stats["keep_frac_sum"] / jnp.maximum(stats["n_routed"], 1.0)
    metrics = {"loss": loss, "xent": xent, "router_loss": router_loss,
               "moe_lb_loss": moe_lb, "keep_frac": keep}
    return loss, metrics


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------

def _pad_cache_to(cache: Dict, T: int, pad_to: int, cfg: ModelConfig) -> Dict:
    """Grow dense KV leaves from length T to pad_to (decode headroom)."""
    def one(path, leaf):
        names = [getattr(p, "key", None) for p in path]
        if names and names[-1] in ("k", "v"):
            axis = leaf.ndim - 3                  # [.., T, Hkv, dh]
            if leaf.shape[axis] == T and pad_to > T:
                pads = [(0, 0)] * leaf.ndim
                pads[axis] = (0, pad_to - T)
                return jnp.pad(leaf, pads)
        return leaf

    return jax.tree_util.tree_map_with_path(one, cache)


def _prefill_hidden(params: Params, batch: Dict[str, jnp.ndarray],
                    cfg: ModelConfig) -> Tuple[jnp.ndarray, Dict, Dict]:
    """Final-normed hidden states [B, T, D] of a full forward, with the
    per-layer caches and stats."""
    if cfg.frontend == "token":
        B, T = batch["tokens"].shape
    else:
        B, T = batch["embeds"].shape[:2]
    positions = _positions(batch, B, T, cfg)
    x = _embed_inputs(params, batch, positions, cfg)
    # named_scope: groups the prompt-phase stack in device profiles (the
    # engine's TraceAnnotation covers the host-side dispatch)
    with jax.named_scope("prefill_stack"):
        x, stats, cache, sq = _apply_stack(params, x, positions, cfg, None,
                                           False, True)
    x = layers.norm_apply(params["final_norm"], x, cfg, stats=sq)
    return x, cache, stats


def sequence_logits(params: Params, batch: Dict[str, jnp.ndarray],
                    cfg: ModelConfig) -> jnp.ndarray:
    """Next-token logits [B, T, V] at every position of a full forward
    (teacher forcing): what checks a decode path's greedy tokens against
    the model, position by position."""
    x, _, _ = _prefill_hidden(params, batch, cfg)
    return layers.unembed(params["embed"], params.get("lm_head"), x, cfg)


def prefill(params: Params, batch: Dict[str, jnp.ndarray], cfg: ModelConfig,
            pad_to: Optional[int] = None,
            last_index: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, Dict, Dict]:
    """Returns (last-position logits [B, V], cache, stats).

    ``last_index``: optional [B] int32 index of each sequence's final *real*
    token — bucketed prefill right-pads prompts to a shared length, and the
    next-token logits must come from the real last position, not the pad."""
    x, cache, stats = _prefill_hidden(params, batch, cfg)
    B, T = x.shape[:2]
    if last_index is None:
        xl = x[:, -1:, :]
    else:
        xl = x[jnp.arange(B), last_index.astype(jnp.int32)][:, None, :]
    logits = layers.unembed(params["embed"], params.get("lm_head"),
                            xl, cfg)[:, 0]
    if pad_to is not None:
        cache = _pad_cache_to(cache, T, pad_to, cfg)
    return logits, cache, stats


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def init_decode_cache(cfg: ModelConfig, batch: int, max_len: int,
                      dtype=None) -> Dict:
    """Zero caches shaped for decode-only lowering (the dry-run's
    ``decode_*`` shapes: one new token against a seq_len-deep cache)."""
    dt = jnp.dtype(dtype or cfg.dtype)
    Hkv, dh = cfg.num_kv_heads, cfg.resolved_head_dim
    di, g, n = cfg.d_inner_ssm, cfg.ssm_groups, cfg.ssm_state
    nh, pd = cfg.ssm_nheads, cfg.ssm_headdim

    def entry(kind: str) -> Dict[str, jnp.ndarray]:
        if kind == MAMBA:
            return {
                "conv_x": jnp.zeros((batch, cfg.ssm_conv - 1, di), dt),
                "conv_bc": jnp.zeros((batch, cfg.ssm_conv - 1, 2 * g * n), dt),
                "ssm": jnp.zeros((batch, nh, pd, n), jnp.float32),
            }
        L = (min(cfg.window_size, max_len) if kind == LOCAL and cfg.window_size
             else max_len)
        if cfg.kv_cache_layout == "bhtd" and not (
                kind == LOCAL and cfg.window_size):
            return {"k": jnp.zeros((batch, Hkv, L, dh), dt),
                    "v": jnp.zeros((batch, Hkv, L, dh), dt)}
        return {"k": jnp.zeros((batch, L, Hkv, dh), dt),
                "v": jnp.zeros((batch, L, Hkv, dh), dt)}

    stage = {f"pos{k}": entry(cfg.block_kind(k)) for k in range(cfg.stage_len)}
    cache: Dict[str, Any] = {"stage0": stage}
    if cfg.num_stages > 1:
        cache["stages"] = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(
                a[None], (cfg.num_stages - 1,) + a.shape), stage)
    return cache


def init_chunk_cache(cfg: ModelConfig, batch: int, cap_len: int,
                     dtype=None) -> Dict:
    """Staging cache for chunked (resumable) prefill: per-layer dense KV
    views in *prefill layout* ([B, cap_len, Hkv, dh] time-major regardless
    of ``cfg.kv_cache_layout`` — the layout ``prefill`` collects, which
    ``serve.engine.pool_insert`` / ``kvcache.paged.pack_prefill`` already
    consume).  ``cap_len`` is normally ``max_len`` rounded up to a chunk
    multiple so the right-padded final chunk always fits.  Only valid for
    all-global-attn stacks (``serve.scheduler.can_chunk_prefill``)."""
    dt = jnp.dtype(dtype or cfg.dtype)
    Hkv, dh = cfg.num_kv_heads, cfg.resolved_head_dim

    def entry(kind: str) -> Dict[str, jnp.ndarray]:
        if kind != ATTN:
            raise ValueError(
                f"chunked prefill requires an all-global-attn stack; "
                f"got a {kind!r} layer")
        return {"k": jnp.zeros((batch, cap_len, Hkv, dh), dt),
                "v": jnp.zeros((batch, cap_len, Hkv, dh), dt)}

    stage = {f"pos{k}": entry(cfg.block_kind(k)) for k in range(cfg.stage_len)}
    cache: Dict[str, Any] = {"stage0": stage}
    if cfg.num_stages > 1:
        cache["stages"] = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(
                a[None], (cfg.num_stages - 1,) + a.shape), stage)
    return cache


def slice_cache_time(cache: Dict, length: int) -> Dict:
    """Truncate dense KV leaves to ``length`` along time (the inverse of
    ``_pad_cache_to`` — used to shed a chunked-prefill staging cache's
    chunk-multiple overhang before pool insertion)."""
    def one(path, leaf):
        names = [getattr(p, "key", None) for p in path]
        if names and names[-1] in ("k", "v"):
            axis = leaf.ndim - 3                  # [.., T, Hkv, dh]
            if leaf.shape[axis] > length:
                return jax.lax.slice_in_dim(leaf, 0, length, axis=axis)
        return leaf

    return jax.tree_util.tree_map_with_path(one, cache)


def _chunk_stack(params: Params, cache: Dict, batch: Dict[str, jnp.ndarray],
                 t0: jnp.ndarray, cfg: ModelConfig
                 ) -> Tuple[jnp.ndarray, Dict, Dict]:
    """Shared stack pass of ``prefill_chunk`` / ``verify_chunk``: C tokens
    at offset ``t0`` over the chunk staging cache, appending each layer's
    merged KV view at [t0, t0+C).  Returns (final-normed activations
    [B, C, D], new cache, stats) with ``stats['attn_gate']``
    [n_attn_layers, B, C]."""
    B, C = batch["tokens"].shape if cfg.frontend == "token" \
        else batch["embeds"].shape[:2]
    t0 = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(t0, jnp.int32)), (B,))
    pos = t0[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    if cfg.pos_embedding == "mrope":
        pos = jnp.broadcast_to(pos[None], (3, B, C))
    x = _embed_inputs(params, batch, pos, cfg)

    stack = params["stack"]
    x, kv_prev, c0, stats, sq = transformer.stage_prefill_chunk(
        stack["stage0"], cache["stage0"], x, None, t0, pos, cfg)
    gates = stats.pop("attn_gate", None)      # [nA_stage, B, C]
    new_cache: Dict[str, Any] = {"stage0": c0}

    if cfg.num_stages > 1:
        def body(carry, xs):
            x, kv_prev, sq = carry
            sp, ce = xs
            x, kv_prev, c, s, sq = transformer.stage_prefill_chunk(
                sp, ce, x, kv_prev, t0, pos, cfg, carried_sq=sq)
            g = s.pop("attn_gate", None)
            return (x, kv_prev, sq), (c, s, g)

        if cfg.scan_layers:
            (x, kv_prev, sq), (cs, s_scan, g_scan) = jax.lax.scan(
                body, (x, kv_prev, sq), (stack["stages"], cache["stages"]))
            new_cache["stages"] = cs
            stats = jax.tree_util.tree_map(lambda a, b: a + b.sum(axis=0),
                                           stats, s_scan)
            gates = jnp.concatenate([gates[None], g_scan], axis=0)
        else:
            c_list, g_list = [], []
            for i in range(cfg.num_stages - 1):
                sl = lambda l: l[i]
                xs = (jax.tree_util.tree_map(sl, stack["stages"]),
                      jax.tree_util.tree_map(sl, cache["stages"]))
                (x, kv_prev, sq), (c, s, g) = body((x, kv_prev, sq), xs)
                stats = jax.tree_util.tree_map(lambda a, b: a + b, stats, s)
                c_list.append(c)
                g_list.append(g)
            new_cache["stages"] = jax.tree_util.tree_map(
                lambda *ls: jnp.stack(ls), *c_list)
            gates = jnp.concatenate(
                [gates[None]] + [g[None] for g in g_list], axis=0)
        # [S, nA_stage, B, C] -> [L_attn, B, C] in stack order
        gates = gates.reshape((-1,) + gates.shape[-2:])

    stats["attn_gate"] = gates
    x = layers.norm_apply(params["final_norm"], x, cfg, stats=sq)
    return x, new_cache, stats


def prefill_chunk(params: Params, cache: Dict, batch: Dict[str, jnp.ndarray],
                  t0: jnp.ndarray, cfg: ModelConfig,
                  last_index: Optional[jnp.ndarray] = None
                  ) -> Tuple[jnp.ndarray, Dict, Dict]:
    """One chunk of resumable prefill: C tokens appended at offset ``t0``.

    The C-token sibling of ``decode_step``: ``cache`` (from
    ``init_chunk_cache``) holds every layer's dense KV view of positions
    [0, t0); this call computes the chunk's activations attending over
    cached-prefix + chunk, appends each layer's merged view at
    [t0, t0+C), and returns (logits [B, V] at ``last_index`` within the
    chunk (default: the chunk's final position), new cache, stats).
    ``stats['attn_gate']`` is [n_attn_layers, B, C] — the same per-token
    execution-gate log monolithic ``prefill`` emits, chunk column-slice
    by column-slice, so paged entry packing is unchanged.  Requires
    masked-mode routing on an all-global-attn stack; the final chunk may
    be right-padded (pass ``last_index`` = real length − 1) — pad columns
    compute garbage that causal masking keeps out of every real token."""
    x, new_cache, stats = _chunk_stack(params, cache, batch, t0, cfg)
    B = x.shape[0]
    if last_index is None:
        xl = x[:, -1:, :]
    else:
        xl = x[jnp.arange(B), last_index.astype(jnp.int32)][:, None, :]
    logits = layers.unembed(params["embed"], params.get("lm_head"),
                            xl, cfg)[:, 0]
    return logits, new_cache, stats


def verify_chunk(params: Params, cache: Dict, batch: Dict[str, jnp.ndarray],
                 t0: jnp.ndarray, cfg: ModelConfig
                 ) -> Tuple[jnp.ndarray, Dict, Dict]:
    """Speculative verification: ``prefill_chunk`` with *every* column
    unembedded.  Feeding the window [f0, d_1..d_k] at positions
    [t0, t0+k] returns logits [B, k+1, V] whose column j is the
    verifier's next-token distribution after the prefix ending at the
    j-th fed token — so column j judges draft d_{j+1} and column ``a``
    supplies the correction after accepting ``a`` drafts
    (``serve/sampling.py``).  KV for the whole window lands at
    [t0, t0+C) exactly like a prefill chunk; rows past the accepted
    prefix are dead weight the next window overwrites, masked until then
    by decode's ``kv_valid_len`` (docs/speculative.md)."""
    x, new_cache, stats = _chunk_stack(params, cache, batch, t0, cfg)
    logits = layers.unembed(params["embed"], params.get("lm_head"), x, cfg)
    return logits, new_cache, stats


def decode_step(params: Params, cache: Dict, batch: Dict[str, jnp.ndarray],
                t: jnp.ndarray, cfg: ModelConfig
                ) -> Tuple[jnp.ndarray, Dict, Dict]:
    """One token for every sequence.  batch: {'tokens': [B, 1]} (or
    {'embeds': [B, 1, D]}); t: [B] int32 per-sequence positions — a scalar
    broadcasts to the whole batch (lock-step decode).  Returns
    (logits [B, V], new cache, stats); ``stats['attn_gate']`` is the
    [n_attn_layers, B] execution-gate log over the attention stack."""
    if cfg.frontend == "token":
        B = batch["tokens"].shape[0]
    else:
        B = batch["embeds"].shape[0]
    t = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(t, jnp.int32)), (B,))
    pos = t[:, None]
    if cfg.pos_embedding == "mrope":
        pos = jnp.broadcast_to(pos[None], (3, B, 1))
    x = _embed_inputs(params, batch, pos, cfg)

    stack = params["stack"]
    x, kv_prev, c0, stats, sq = transformer.stage_decode(
        stack["stage0"], cache["stage0"], x, None, t, pos, cfg)
    g0 = stats.pop("attn_gate", None)
    gates = g0                      # [nA, B] or None (attention-free stage)
    new_cache: Dict[str, Any] = {"stage0": c0}

    if cfg.num_stages > 1:
        def body(carry, xs):
            x, kv_prev, sq = carry
            sp, ce = xs
            x, kv_prev, c, s, sq = transformer.stage_decode(
                sp, ce, x, kv_prev, t, pos, cfg, carried_sq=sq)
            g = s.pop("attn_gate", None)
            return (x, kv_prev, sq), (c, s, g)

        if cfg.scan_layers:
            (x, kv_prev, sq), (cs, s_scan, g_scan) = jax.lax.scan(
                body, (x, kv_prev, sq), (stack["stages"], cache["stages"]))
            new_cache["stages"] = cs
            stats = jax.tree_util.tree_map(lambda a, b: a + b.sum(axis=0),
                                           stats, s_scan)
            if gates is not None:
                gates = jnp.concatenate([gates[None], g_scan], axis=0)
        else:
            c_list, g_list = [], []
            for i in range(cfg.num_stages - 1):
                sl = lambda l: l[i]
                xs = (jax.tree_util.tree_map(sl, stack["stages"]),
                      jax.tree_util.tree_map(sl, cache["stages"]))
                (x, kv_prev, sq), (c, s, g) = body((x, kv_prev, sq), xs)
                stats = jax.tree_util.tree_map(lambda a, b: a + b, stats, s)
                c_list.append(c)
                g_list.append(g)
            new_cache["stages"] = jax.tree_util.tree_map(
                lambda *ls: jnp.stack(ls), *c_list)
            if gates is not None:
                gates = jnp.concatenate(
                    [gates[None]] + [g[None] for g in g_list], axis=0)
        if gates is not None:
            # [S, nA, B] -> [L_attn, B] in stack order (stage0 first)
            gates = gates.reshape(-1, B)

    if gates is not None:
        stats["attn_gate"] = gates
    # the last block's fused epilogue already produced the final norm's
    # reduction (incremental-reduction carry)
    x = layers.norm_apply(params["final_norm"], x, cfg, stats=sq)
    logits = layers.unembed(params["embed"], params.get("lm_head"), x, cfg)
    return logits[:, 0], new_cache, stats


def paged_decode_step(params: Params, store: Dict,
                      batch: Dict[str, jnp.ndarray], t: jnp.ndarray,
                      block_table: jnp.ndarray, fill: jnp.ndarray,
                      cfg: ModelConfig,
                      commit_mask: Optional[jnp.ndarray] = None
                      ) -> Tuple[jnp.ndarray, Dict, Dict]:
    """One token for every slot against the paged KV store.

    The dense-pool twin of ``decode_step``: past tokens' KV lives in the
    shared store-once entry stream (``repro/kvcache/paged.py``) instead of
    per-layer ``[B, Tmax]`` caches.  ``block_table`` [B, J] and ``fill``
    [B] come from the host-side ``PageAllocator`` (which has proactively
    guaranteed page capacity for this step's ≤ n_attn_layers appends).
    Slots with ``fill == 0`` are inactive: they decode garbage but commit
    nothing.  ``commit_mask`` [B] overrides that default commit gate —
    ``paged_decode_loop`` passes its per-slot active mask so a slot that
    finishes mid-loop stops appending entries.  Returns (logits [B, V],
    new store, stats) with ``stats['attn_gate']`` as in ``decode_step``."""
    from repro.kvcache import paged as paged_mod

    assert paged_mod.can_page(cfg), f"{cfg.name}: not a pageable stack"
    if cfg.frontend == "token":
        B = batch["tokens"].shape[0]
    else:
        B = batch["embeds"].shape[0]
    t = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(t, jnp.int32)), (B,))
    pos = t[:, None]
    if cfg.pos_embedding == "mrope":
        pos = jnp.broadcast_to(pos[None], (3, B, 1))
    x = _embed_inputs(params, batch, pos, cfg)

    # resolve the page chains once per step (the store is frozen until the
    # end-of-step commit; the current token rides along as an explicit
    # (k_t, v_t) pair inside each layer)
    kv_dtype = paged_mod.infer_kv_dtype(store, cfg)
    view = paged_mod.gather_view(store, block_table,
                                 with_kv=not cfg.use_kernels,
                                 kv_dtype=kv_dtype)
    E = view["pos"].shape[1]
    paged_ctx = dict(view)
    paged_ctx["in_fill"] = jnp.arange(E)[None, :] < fill[:, None]
    if cfg.use_kernels:
        paged_ctx["k_pages"] = store["k_pages"]
        paged_ctx["v_pages"] = store["v_pages"]
        paged_ctx["block_table"] = block_table
        if kv_dtype is not None:
            paged_ctx["k_scales"] = store["k_scales"]
            paged_ctx["v_scales"] = store["v_scales"]

    stack = params["stack"]
    nA_stage = sum(1 for k in range(cfg.stage_len)
                   if cfg.block_kind(k) != MAMBA)
    x, kv_prev, s0, sq = transformer.stage_decode_paged(
        stack["stage0"], x, None, t, pos, cfg, paged_ctx,
        jnp.int32(0))
    gates = s0.pop("attn_gate")
    buf_k, buf_v = s0.pop("kv_token")
    stats = s0

    if cfg.num_stages > 1:
        def body(carry, xs):
            x, kv_prev, sq = carry
            sp, si = xs
            x, kv_prev, s, sq = transformer.stage_decode_paged(
                sp, x, kv_prev, t, pos, cfg, paged_ctx, si * nA_stage,
                carried_sq=sq)
            g = s.pop("attn_gate")
            kt = s.pop("kv_token")
            return (x, kv_prev, sq), (s, g, kt)

        idxs = jnp.arange(1, cfg.num_stages, dtype=jnp.int32)
        if cfg.scan_layers:
            (x, kv_prev, sq), (s_scan, g_scan, kt_scan) = jax.lax.scan(
                body, (x, kv_prev, sq), (stack["stages"], idxs))
            stats = jax.tree_util.tree_map(lambda a, b: a + b.sum(axis=0),
                                           stats, s_scan)
            gates = jnp.concatenate([gates[None], g_scan], axis=0)
            buf_k = jnp.concatenate([buf_k[None], kt_scan[0]], axis=0)
            buf_v = jnp.concatenate([buf_v[None], kt_scan[1]], axis=0)
        else:
            g_list, k_list, v_list = [], [], []
            for i in range(cfg.num_stages - 1):
                sp = jax.tree_util.tree_map(lambda l: l[i], stack["stages"])
                (x, kv_prev, sq), (s, g, kt) = body((x, kv_prev, sq),
                                                    (sp, idxs[i]))
                stats = jax.tree_util.tree_map(lambda a, b: a + b, stats, s)
                g_list.append(g[None])
                k_list.append(kt[0][None])
                v_list.append(kt[1][None])
            gates = jnp.concatenate([gates[None]] + g_list, axis=0)
            buf_k = jnp.concatenate([buf_k[None]] + k_list, axis=0)
            buf_v = jnp.concatenate([buf_v[None]] + v_list, axis=0)
        gates = gates.reshape(-1, B)
        buf_k = buf_k.reshape((-1,) + buf_k.shape[-3:])
        buf_v = buf_v.reshape((-1,) + buf_v.shape[-3:])

    if commit_mask is None:
        commit_mask = fill > 0
    store = paged_mod.commit_decode(store, buf_k, buf_v, gates, t,
                                    block_table, fill, commit_mask, cfg,
                                    kv_dtype=kv_dtype)
    stats["attn_gate"] = gates
    x = layers.norm_apply(params["final_norm"], x, cfg, stats=sq)
    logits = layers.unembed(params["embed"], params.get("lm_head"), x, cfg)
    return logits[:, 0], store, stats


# ---------------------------------------------------------------------------
# Device-resident multi-step decode (one jitted dispatch per N tokens)
# ---------------------------------------------------------------------------

def _entry_active(feed: jnp.ndarray, active: jnp.ndarray,
                  stop: jnp.ndarray) -> jnp.ndarray:
    """A deferred first token (sampled inside the prefill dispatch, never
    seen by the host) may itself be the stop token: kill the slot before
    it decodes, so it emits nothing and appends no KV."""
    return active & ~((stop >= 0) & (feed == stop))


def _loop_finish(tok: jnp.ndarray, t: jnp.ndarray, emitted: jnp.ndarray,
                 active: jnp.ndarray, budget: jnp.ndarray,
                 stop: jnp.ndarray, max_len: int) -> jnp.ndarray:
    """Per-slot finish detection, replicating the host engine's
    ``_advance_slot`` conditions: stop token sampled, generation budget
    exhausted (``emitted`` already counts this step's token), or the next
    write position reaching the pool's max_len."""
    hit_stop = (stop >= 0) & (tok == stop)
    return active & ~(hit_stop | (emitted >= budget) | (t + 1 >= max_len))


def decode_loop(params: Params, cache: Dict, feed: jnp.ndarray,
                t: jnp.ndarray, active: jnp.ndarray, budget: jnp.ndarray,
                stop: jnp.ndarray, rng: jnp.ndarray, *, n_steps: int,
                cfg: ModelConfig, max_len: int, temperature: float = 0.0,
                top_k: int = 0) -> Tuple[Dict, Dict]:
    """``n_steps`` fused decode iterations under one jit (``lax.scan``):
    per-step token sampling, stop-token/length detection and position
    advance all happen on device, so the host syncs once per dispatch
    instead of once per token (the serving-loop analogue of the paper's
    latency hiding: control decisions overlap in-flight compute).

    Inputs (all [B] over the slot pool): ``feed`` the token each slot
    feeds next, ``t`` its write position, ``active`` slot liveness,
    ``budget`` how many tokens the slot may still emit, ``stop`` its stop
    token id (-1 = none).  A slot that finishes mid-loop freezes its
    (feed, t) pair: every subsequent iteration then recomputes — and
    rewrites, bit-identically — the KV entry it already wrote at ``t``
    instead of appending, so a finished slot stops growing its cache row
    with no per-step host intervention.  Inactive slots compute garbage
    that never escapes: their sampled tokens are masked by
    ``step_active`` and their KV rewrite is idempotent.

    Returns (new cache, out) with stacked per-step outputs —
    ``tokens``/``step_active`` [n_steps, B], ``attn_gate``
    [n_steps, L_attn, B] (None for gate-free stacks) — plus the final
    ``feed``/``t``/``active``/``emitted`` carry and the advanced ``rng``
    (one split per step, mirroring the single-step engine's sequence)."""
    from repro.serve.sampling import split_sample

    feed = jnp.asarray(feed, jnp.int32)
    t = jnp.asarray(t, jnp.int32)
    budget = jnp.asarray(budget, jnp.int32)
    stop = jnp.asarray(stop, jnp.int32)
    active = _entry_active(feed, jnp.asarray(active, bool), stop)

    def body(carry, _):
        cache, feed, t, active, emitted, rng = carry
        logits, cache, stats = decode_step(
            params, cache, {"tokens": feed[:, None]}, t, cfg)
        rng, tok = split_sample(logits, rng, temperature, top_k)
        emitted = emitted + active.astype(jnp.int32)
        nxt = _loop_finish(tok, t, emitted, active, budget, stop, max_len)
        ys = (tok, active, stats.get("attn_gate"))
        feed = jnp.where(nxt, tok, feed)
        t = jnp.where(nxt, t + 1, t)
        return (cache, feed, t, nxt, emitted, rng), ys

    init = (cache, feed, t, active, jnp.zeros_like(budget), rng)
    with jax.named_scope(f"decode_epoch_x{n_steps}"):
        (cache, feed, t, active, emitted, rng), \
            (toks, step_active, gates) = \
            jax.lax.scan(body, init, None, length=n_steps)
    return cache, {"tokens": toks, "step_active": step_active,
                   "attn_gate": gates, "feed": feed, "t": t,
                   "active": active, "emitted": emitted, "rng": rng}


def paged_decode_loop(params: Params, store: Dict, feed: jnp.ndarray,
                      t: jnp.ndarray, fill: jnp.ndarray,
                      active: jnp.ndarray, budget: jnp.ndarray,
                      stop: jnp.ndarray, rng: jnp.ndarray,
                      block_table: jnp.ndarray, *, n_steps: int,
                      cfg: ModelConfig, max_len: int,
                      temperature: float = 0.0, top_k: int = 0
                      ) -> Tuple[Dict, Dict]:
    """``decode_loop``'s paged-store twin: N fused ``paged_decode_step``
    iterations with the entry-stream fill advancing on device — each
    active slot appends its measured fresh-entry count (layer-0 dense +
    executed layers, exactly the host ``PageAllocator`` accounting the
    engine replays from the returned gate log after the sync).  A slot
    that finishes mid-loop drops out of the commit mask, so it stops
    appending entries; the host must have pre-reserved page headroom for
    ``n_steps`` worst-case appends per active slot (``block_table`` must
    span that reservation).  Returns (new store, out) as ``decode_loop``
    plus the final per-slot ``fill``."""
    from repro.kvcache import history as history_mod
    from repro.kvcache import paged as paged_mod
    from repro.serve.sampling import split_sample

    reuse = paged_mod.reuse_enabled(cfg)
    feed = jnp.asarray(feed, jnp.int32)
    t = jnp.asarray(t, jnp.int32)
    fill = jnp.asarray(fill, jnp.int32)
    budget = jnp.asarray(budget, jnp.int32)
    stop = jnp.asarray(stop, jnp.int32)
    active = _entry_active(feed, jnp.asarray(active, bool), stop)

    def body(carry, _):
        store, feed, t, fill, active, emitted, rng = carry
        logits, store, stats = paged_decode_step(
            params, store, {"tokens": feed[:, None]}, t, block_table, fill,
            cfg, commit_mask=active & (fill > 0))
        rng, tok = split_sample(logits, rng, temperature, top_k)
        gates = stats["attn_gate"]                             # [nA, B]
        n_fresh = history_mod.fresh_mask(gates, reuse).astype(
            jnp.int32).sum(axis=0)
        fill = fill + jnp.where(active, n_fresh, 0)
        emitted = emitted + active.astype(jnp.int32)
        nxt = _loop_finish(tok, t, emitted, active, budget, stop, max_len)
        ys = (tok, active, gates)
        feed = jnp.where(nxt, tok, feed)
        t = jnp.where(nxt, t + 1, t)
        return (store, feed, t, fill, nxt, emitted, rng), ys

    init = (store, feed, t, fill, active, jnp.zeros_like(budget), rng)
    with jax.named_scope(f"paged_decode_epoch_x{n_steps}"):
        (store, feed, t, fill, active, emitted, rng), \
            (toks, step_active, gates) = jax.lax.scan(body, init, None,
                                                      length=n_steps)
    return store, {"tokens": toks, "step_active": step_active,
                   "attn_gate": gates, "feed": feed, "t": t, "fill": fill,
                   "active": active, "emitted": emitted, "rng": rng}


# ---------------------------------------------------------------------------
# Speculative decoding: draft loops + paged verify/commit
# ---------------------------------------------------------------------------

def draft_loop(params: Params, cache: Dict, feed: jnp.ndarray,
               t: jnp.ndarray, rng: jnp.ndarray, *, n_steps: int,
               cfg: ModelConfig, temperature: float = 0.0,
               top_k: int = 0) -> Tuple[Dict, Dict]:
    """Speculative draft: ``n_steps`` fused decode iterations under the
    (usually skip-biased) draft parameters, proposing one token per step.

    Unlike ``decode_loop`` there is no stop/budget/length masking: a
    window is short (γ ≤ spec_k, pre-clamped by the host against
    max_len) and the host truncates emission at acceptance time, so a
    draft chain running past a stop token is dead weight, never an
    error.  Per-step draft *logits* are stacked alongside the tokens so
    temperature>0 acceptance can reconstruct the exact draft
    distribution each proposal was drawn from.  Draft KV lands in the
    cache rows the verify chunk immediately overwrites.  Returns
    (cache, out): ``tokens`` [n, B], ``logits`` [n, B, V], final
    ``feed``/``t`` and the advanced ``rng``."""
    from repro.serve.sampling import split_sample

    feed = jnp.asarray(feed, jnp.int32)
    t = jnp.asarray(t, jnp.int32)

    def body(carry, _):
        cache, feed, t, rng = carry
        logits, cache, _ = decode_step(
            params, cache, {"tokens": feed[:, None]}, t, cfg)
        rng, tok = split_sample(logits, rng, temperature, top_k)
        return (cache, tok, t + 1, rng), (tok, logits)

    with jax.named_scope(f"draft_x{n_steps}"):
        (cache, feed, t, rng), (toks, logits) = jax.lax.scan(
            body, (cache, feed, t, rng), None, length=n_steps)
    return cache, {"tokens": toks, "logits": logits, "feed": feed,
                   "t": t, "rng": rng}


def paged_draft_loop(params: Params, store: Dict, feed: jnp.ndarray,
                     t: jnp.ndarray, fill: jnp.ndarray,
                     active: jnp.ndarray, rng: jnp.ndarray,
                     block_table: jnp.ndarray, *, n_steps: int,
                     cfg: ModelConfig, temperature: float = 0.0,
                     top_k: int = 0) -> Tuple[Dict, Dict]:
    """``draft_loop`` against the paged store: tentative entries append
    at the live fill (the committed prefix below the window's entry
    count stays untouched), fill advancing on device via the measured
    fresh-entry count.  Every entry appended here is *tentative*:
    ``paged_verify_chunk`` reads only the pre-window prefix, and
    ``commit_verified`` rewrites the stream from the pre-window fill
    with verifier KV for the accepted columns only — so a rejected
    draft leaves no live residue (docs/speculative.md).  The host must
    have pre-reserved page headroom for ``n_steps`` worst-case appends.
    Returns the final ``fill`` so the host can count rolled-back
    entries."""
    from repro.kvcache import history as history_mod
    from repro.kvcache import paged as paged_mod
    from repro.serve.sampling import split_sample

    reuse = paged_mod.reuse_enabled(cfg)
    feed = jnp.asarray(feed, jnp.int32)
    t = jnp.asarray(t, jnp.int32)
    fill = jnp.asarray(fill, jnp.int32)
    active = jnp.asarray(active, bool)

    def body(carry, _):
        store, feed, t, fill, rng = carry
        logits, store, stats = paged_decode_step(
            params, store, {"tokens": feed[:, None]}, t, block_table, fill,
            cfg, commit_mask=active & (fill > 0))
        rng, tok = split_sample(logits, rng, temperature, top_k)
        n_fresh = history_mod.fresh_mask(stats["attn_gate"], reuse).astype(
            jnp.int32).sum(axis=0)
        fill = fill + jnp.where(active, n_fresh, 0)
        return (store, tok, t + 1, fill, rng), (tok, logits)

    with jax.named_scope(f"paged_draft_x{n_steps}"):
        (store, feed, t, fill, rng), (toks, logits) = jax.lax.scan(
            body, (store, feed, t, fill, rng), None, length=n_steps)
    return store, {"tokens": toks, "logits": logits, "feed": feed,
                   "t": t, "fill": fill, "rng": rng}


def paged_verify_chunk(params: Params, store: Dict,
                       batch: Dict[str, jnp.ndarray], t0: jnp.ndarray,
                       block_table: jnp.ndarray, fill: jnp.ndarray,
                       cfg: ModelConfig) -> Tuple[jnp.ndarray, Dict]:
    """Speculative verification against the paged store — read-only.

    The C-token sibling of ``paged_decode_step``: the window's C = k+1
    fed tokens attend over the *committed* entry prefix (entries below
    ``fill`` — the engine passes the pre-draft fill, so the draft loop's
    tentative entries are invisible here) plus the window's own
    in-flight KV, which rides along explicitly inside each layer.
    Nothing is committed: the per-layer token views come back in
    ``stats['kv_token']`` ([nA, B, C, Hkv, dh] each) for
    ``commit_verified`` to append after host-side acceptance.  Returns
    (logits [B, C, V], stats) with ``stats['attn_gate']`` [nA, B, C]."""
    from repro.kvcache import paged as paged_mod

    assert paged_mod.can_page(cfg), f"{cfg.name}: not a pageable stack"
    B, C = batch["tokens"].shape if cfg.frontend == "token" \
        else batch["embeds"].shape[:2]
    t0 = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(t0, jnp.int32)), (B,))
    pos = t0[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    if cfg.pos_embedding == "mrope":
        pos = jnp.broadcast_to(pos[None], (3, B, C))
    x = _embed_inputs(params, batch, pos, cfg)

    # always the jnp concat path: the Pallas decode kernel is
    # single-query, and a k+1-wide window doesn't need it
    view = paged_mod.gather_view(store, block_table, with_kv=True,
                                 kv_dtype=paged_mod.infer_kv_dtype(store,
                                                                   cfg))
    E = view["pos"].shape[1]
    paged_ctx = dict(view)
    paged_ctx["in_fill"] = jnp.arange(E)[None, :] < fill[:, None]

    stack = params["stack"]
    nA_stage = sum(1 for k in range(cfg.stage_len)
                   if cfg.block_kind(k) != MAMBA)
    x, kv_prev, s0, sq = transformer.stage_verify_paged(
        stack["stage0"], x, None, pos, cfg, paged_ctx, jnp.int32(0))
    gates = s0.pop("attn_gate")
    buf_k, buf_v = s0.pop("kv_token")
    stats = s0

    if cfg.num_stages > 1:
        def body(carry, xs):
            x, kv_prev, sq = carry
            sp, si = xs
            x, kv_prev, s, sq = transformer.stage_verify_paged(
                sp, x, kv_prev, pos, cfg, paged_ctx, si * nA_stage,
                carried_sq=sq)
            g = s.pop("attn_gate")
            kt = s.pop("kv_token")
            return (x, kv_prev, sq), (s, g, kt)

        idxs = jnp.arange(1, cfg.num_stages, dtype=jnp.int32)
        if cfg.scan_layers:
            (x, kv_prev, sq), (s_scan, g_scan, kt_scan) = jax.lax.scan(
                body, (x, kv_prev, sq), (stack["stages"], idxs))
            stats = jax.tree_util.tree_map(lambda a, b: a + b.sum(axis=0),
                                           stats, s_scan)
            gates = jnp.concatenate([gates[None], g_scan], axis=0)
            buf_k = jnp.concatenate([buf_k[None], kt_scan[0]], axis=0)
            buf_v = jnp.concatenate([buf_v[None], kt_scan[1]], axis=0)
        else:
            g_list, k_list, v_list = [], [], []
            for i in range(cfg.num_stages - 1):
                sp = jax.tree_util.tree_map(lambda l: l[i], stack["stages"])
                (x, kv_prev, sq), (s, g, kt) = body((x, kv_prev, sq),
                                                    (sp, idxs[i]))
                stats = jax.tree_util.tree_map(lambda a, b: a + b, stats, s)
                g_list.append(g[None])
                k_list.append(kt[0][None])
                v_list.append(kt[1][None])
            gates = jnp.concatenate([gates[None]] + g_list, axis=0)
            buf_k = jnp.concatenate([buf_k[None]] + k_list, axis=0)
            buf_v = jnp.concatenate([buf_v[None]] + v_list, axis=0)
        gates = gates.reshape((-1, B) + gates.shape[-1:])
        buf_k = buf_k.reshape((-1,) + buf_k.shape[-4:])
        buf_v = buf_v.reshape((-1,) + buf_v.shape[-4:])

    stats["attn_gate"] = gates
    stats["kv_token"] = (buf_k, buf_v)
    x = layers.norm_apply(params["final_norm"], x, cfg, stats=sq)
    logits = layers.unembed(params["embed"], params.get("lm_head"), x, cfg)
    return logits, stats


def commit_verified(store: Dict, buf_k: jnp.ndarray, buf_v: jnp.ndarray,
                    gates: jnp.ndarray, t0: jnp.ndarray,
                    block_table: jnp.ndarray, fill0: jnp.ndarray,
                    committed: jnp.ndarray, active: jnp.ndarray,
                    cfg: ModelConfig) -> Tuple[Dict, jnp.ndarray]:
    """Post-acceptance paged commit: rewrite the entry stream from the
    pre-window ``fill0`` with the *verifier's* KV for exactly the
    leading ``committed`` columns of the window (per slot), in the same
    token-major order a never-speculated engine appends — so the
    committed stream is indistinguishable from plain decoding, and every
    tentative draft entry at index ≥ post-commit fill is dead (masked by
    ``in_fill`` at read time, overwritten by the next window's draft).

    buf_k/buf_v: [nA, S, C, Hkv, dh] (``paged_verify_chunk`` views);
    gates: [nA, S, C]; t0/fill0/committed: [S]; ``active`` [S] masks
    slots outside the window.  Returns (store, per-slot post-commit
    fill)."""
    from repro.kvcache import history as history_mod
    from repro.kvcache import paged as paged_mod

    reuse = paged_mod.reuse_enabled(cfg)
    C = gates.shape[-1]
    fill = jnp.asarray(fill0, jnp.int32)
    committed = jnp.asarray(committed, jnp.int32)
    active = jnp.asarray(active, bool)
    t0 = jnp.asarray(t0, jnp.int32)

    kv_dtype = paged_mod.infer_kv_dtype(store, cfg)

    def body(carry, xs):
        store, fill = carry
        bk, bv, g, j = xs
        mask = active & (j < committed)
        store = paged_mod.commit_decode(store, bk, bv, g, t0 + j,
                                        block_table, fill, mask, cfg,
                                        kv_dtype=kv_dtype)
        n_fresh = history_mod.fresh_mask(g, reuse).astype(
            jnp.int32).sum(axis=0)
        fill = fill + jnp.where(mask, n_fresh, 0)
        return (store, fill), None

    xs = (jnp.moveaxis(buf_k, 2, 0), jnp.moveaxis(buf_v, 2, 0),
          jnp.moveaxis(gates, 2, 0), jnp.arange(C, dtype=jnp.int32))
    with jax.named_scope(f"commit_verified_x{C}"):
        (store, fill), _ = jax.lax.scan(body, (store, fill), xs)
    return store, fill
