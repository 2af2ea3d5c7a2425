"""Serving engines: the paper's end-to-end inference pipeline.

prefill (gather/compacted execution) → autoregressive decode with dynamic
routing and cross-layer KV reuse, with KV-storage accounting *measured*
from the per-step execution-gate log (``stats['attn_gate']``) instead of
the analytic keep-rate estimate.

Two engines share the jitted ``model.decode_step`` path:

``ServeEngine``
    Lock-step batch: one fixed batch, every sequence at the same position.
    Kept as the baseline the continuous engine is benchmarked against.

``ContinuousBatchingEngine``
    Slot-based continuous batching (the serving pattern SkipOPU's
    dynamically allocated compute pays off in): a fixed ``max_slots ×
    max_len`` KV pool allocated once, a FIFO request queue with prefill
    length-bucketing, per-sequence decode positions (``t: [B]``), and
    admission/eviction as requests start/stop — see
    ``repro/serve/scheduler.py`` and docs/serving.md.
"""
from __future__ import annotations

import dataclasses
import warnings
from functools import partial, wraps
from time import perf_counter
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import LOCAL, ModelConfig
from repro.core import kv_reuse
from repro.core.routing import draft_router_bias
from repro.distributed.sharding import ShardingPolicy, set_policy
from repro.kernels import fused_linear as fused_linear_mod
from repro.kvcache import history as history_mod
from repro.kvcache import paged as paged_mod
from repro.models import model as model_lib
from repro.obs import (MetricsRegistry, as_tracer, jit_cache_size,
                       request_tid)
from repro.kvcache import prefix as prefix_mod
from repro.serve import snapshot as snapshot_mod
from repro.serve.config import EngineConfig
from repro.serve.errors import (AdmissionRejected, ConfigError, HungDispatch,
                                PageExhausted, SimulatedKill)
from repro.serve.faults import (FaultInjected, Watchdog, as_fault_plan,
                                sleep_stall)
from repro.serve import sampling as sampling_mod
from repro.serve.sampling import sample
from repro.serve.scheduler import (ActiveRequest, PrefillChunk, Request,
                                   Scheduler, can_bucket,
                                   can_chunk_prefill, can_speculate,
                                   default_buckets)

# sentinel distinguishing "caller passed this legacy kwarg" from its old
# default — the deprecation shim only routes *explicit* flat kwargs
# through EngineConfig.from_kwargs
_UNSET = object()

# Every program of the continuous-batching engine rounds where the JAX
# program rounds.  XLA's default excess precision keeps some bf16
# intermediates of a fusion in f32 (a projection's gate multiply and
# residual add, the next norm's sum of squares), and a sharded program
# fuses differently from one device's, so with it a tensor-parallel
# engine would round differently from the unsharded one.
COMPILER_OPTIONS = {"xla_allow_excess_precision": False}
_legacy_warned = False


def _warn_legacy_kwargs(names) -> None:
    """One DeprecationWarning per process, naming the offending kwargs."""
    global _legacy_warned
    if _legacy_warned:
        return
    _legacy_warned = True
    warnings.warn(
        "flat ContinuousBatchingEngine kwargs ({}) are deprecated — pass "
        "config=EngineConfig(...) instead (semantics unchanged; migration "
        "table in docs/serving.md)".format(", ".join(names)),
        DeprecationWarning, stacklevel=3)


@dataclasses.dataclass
class ServeStats:
    """Aggregate engine statistics for one ``run()`` (or one lock-step
    ``generate()``).  Counters are totals over the run; times are wall
    seconds on the host driving the jitted steps.

    Fields:
      prefill_tokens    — prompt tokens prefilled (real tokens; bucket /
                          chunk padding excluded).
      decode_tokens     — tokens emitted (the first token of each request
                          — sampled from prefill logits — included).
      prefill_s         — wall time spent in prefill work (monolithic
                          prefills and prefill chunks alike).
      decode_s          — wall time spent in ragged decode steps.
      prefill_chunks    — prefill work units executed: one per chunk with
                          ``prefill_chunk > 0``, one per prompt otherwise.
      interleaved_steps — engine iterations in which a prefill chunk ran
                          in the same step as resident decodes (the
                          mixed prefill/decode steps chunked prefill
                          exists for; always 0 when no request ever
                          coexists with a prefill).
      attn_keep_frac    — mean decode-time attention keep rate from the
                          execution-gate log (1.0 = dense).
      kv_saved_fraction — measured compact-KV storage saving over this
                          run's execution gates (prompt and decode phases
                          both); ``kv_saved_analytic`` is the
                          configured-keep-rate estimate.
      requests_completed — requests drained to a RequestResult.
      decode_dispatches — jitted decode dispatches: one per ragged step in
                          single-step mode, one per N-step epoch with
                          ``decode_steps > 1`` (the host-overhead counter
                          the fused loop exists to shrink).
      device_s          — wall time the host spent *blocked* on device
                          results (the per-iteration sync); host_s is the
                          rest of the run-loop wall time — planning,
                          admission, bookkeeping and dispatch.  With the
                          fused loop host_s overlaps in-flight device
                          work instead of serializing with it.
      compiles          — jitted-dispatch cache growth observed during
                          the run (new compiled variants: prefill
                          buckets, pow2 epoch lengths, block-table
                          widths).  A steady-state run should show 0.

    All wall-clock fields are ``time.perf_counter`` intervals (monotonic
    — never skewed by NTP adjustment the way ``time.time`` deltas are).

    On the continuous engine this dataclass is a *derived view*: every
    counter field is read out of the run's ``MetricsRegistry`` at
    ``_finalize`` (``run()['metrics']`` exposes the registry itself,
    with histograms, per-layer series and time series the flat
    aggregate cannot hold — see docs/observability.md).

    Paged-mode extras (``kv_mode == "paged"``): page pool geometry
    (``page_size``/``pages_total``), ``pages_peak`` live-footprint peak,
    ``preemptions`` (OOM-safe mid-decode evictions), entry-stream write
    counters (``kv_entries_stored`` vs the per-layer-dense baseline
    ``kv_entries_dense``), and history-buffer hit rates measured from the
    gate log (aggregate + per attention layer)."""
    prefill_tokens: int = 0
    decode_tokens: int = 0
    prefill_s: float = 0.0
    decode_s: float = 0.0
    prefill_chunks: int = 0
    interleaved_steps: int = 0
    attn_keep_frac: float = 1.0
    kv_saved_fraction: float = 0.0        # measured from logged gates
    kv_saved_analytic: float = 0.0        # configured-keep-rate estimate
    requests_completed: int = 0
    # -- host-overhead counters (the fused-epoch loop's scoreboard) --------
    decode_dispatches: int = 0            # jitted decode dispatches (epochs)
    host_s: float = 0.0                   # host planning/bookkeeping wall
    device_s: float = 0.0                 # wall blocked on device syncs
    compiles: int = 0                     # new compiled variants this run
    # -- paged-KV engine mode (kv_mode == "paged") -------------------------
    kv_mode: str = "dense"
    page_size: int = 0
    pages_total: int = 0
    pages_peak: int = 0                   # peak pages in use (live footprint)
    preemptions: int = 0                  # OOM-safe mid-decode evictions
    kv_entries_stored: int = 0            # live compact-store writes
    kv_entries_dense: int = 0             # per-layer-dense baseline writes
    history_hit_rate: float = 0.0         # reads served by the history buf
    history_hits_per_layer: List[float] = dataclasses.field(
        default_factory=list)
    # -- prefix cache (kv.prefix_cache; docs/kvcache.md) -------------------
    prefix_hits: int = 0                  # warm-prefix admissions
    prefix_misses: int = 0                # cold admissions with cache on
    prefix_tokens_saved: int = 0          # prompt tokens skipped at prefill
    prefix_records: int = 0               # records resident at run end
    # -- speculative decoding (spec_k > 0; docs/speculative.md) ------------
    spec_windows: int = 0                 # draft+verify windows dispatched
    spec_tokens_drafted: int = 0          # draft proposals fed to verify
    spec_tokens_accepted: int = 0         # proposals the verifier kept
    spec_entries_rolled_back: int = 0     # tentative paged entries discarded
    spec_acceptance_rate: float = 0.0     # accepted / drafted (0 when off)
    # -- robustness / lifecycle (docs/robustness.md) -----------------------
    faults_injected: int = 0              # FaultPlan faults that fired
    dispatch_retries: int = 0             # iterations abandoned + replanned
    watchdog_strikes: int = 0             # straggler strikes (soft)
    requests_cancelled: int = 0           # finish_reason == "cancelled"
    deadline_exceeded: int = 0            # finish_reason == "deadline"
    requests_shed: int = 0                # submit()-time load shedding
    preempt_budget_exhausted: int = 0     # finish_reason == "preempt_budget"
    epoch_shrinks: int = 0                # adaptive decode_steps halvings
    snapshots: int = 0                    # boundary snapshots written
    resumes: int = 0                      # runs continued from a snapshot

    @property
    def decode_tok_per_s(self) -> float:
        return self.decode_tokens / self.decode_s if self.decode_s else 0.0

    @property
    def kv_entries_saved_fraction(self) -> float:
        """Live storage saving of the paged history buffer (matches the
        CompactKVStore accounting replayed over the same gates)."""
        if not self.kv_entries_dense:
            return 0.0
        return 1.0 - self.kv_entries_stored / self.kv_entries_dense


@dataclasses.dataclass
class RequestResult:
    """Per-request outcome + serving metrics.

    Fields:
      uid          — id returned by ``submit``.
      tokens       — generated token ids, stop token (if hit) included.
      prompt_len   — real prompt length T0 (padding excluded).
      ttft_s       — wall seconds from the request's ``submit`` to the
                     first token of its last admission (a preempted
                     request re-prefills).  Under monolithic prefill
                     that is queue wait + one prefill; under chunked
                     prefill (``prefill_chunk > 0``) it spans all
                     ceil(T0/chunk) chunk steps *plus* the decode steps
                     interleaved between them — chunking deliberately
                     trades a little TTFT on the prefilling request for
                     bounded decode stalls on every resident one.
      decode_s     — wall seconds inside decode steps this request
                     participated in (other requests' prefill work
                     excluded).
      max_decode_stall_s — longest wall-clock gap between two of this
                     request's consecutive token emissions; the
                     head-of-line metric chunked prefill bounds (an
                     eager monolithic prefill of a long newcomer shows
                     up here for every resident).
      finish_reason — why generation ended:
                     "length" (budget), "stop" (stop token), "max_len"
                     (slot position hit the pool's max_len); or a
                     lifecycle outcome — "deadline" (per-request deadline
                     elapsed; tokens are the partial output), "cancelled"
                     (cooperative cancellation honored at a step/epoch
                     boundary), "preempt_budget" (preempted more than the
                     engine's ``max_preemptions`` retry budget allows).
      kv_stored / kv_dense — measured compact-store entry writes vs the
                     per-layer-dense baseline for this request's decode
                     steps."""
    uid: int
    tokens: np.ndarray                   # generated tokens (incl. stop token)
    prompt_len: int
    ttft_s: float                        # submit → first token
    decode_s: float                      # time in this request's decode steps
    finish_reason: str                   # "length"|"stop"|"max_len"|...
    kv_stored: int = 0                   # measured compact-store entries
    kv_dense: int = 0                    # dense-baseline entries
    max_decode_stall_s: float = 0.0      # worst inter-token emission gap

    @property
    def decode_tokens(self) -> int:
        return int(self.tokens.shape[0])

    @property
    def decode_tok_per_s(self) -> float:
        n = self.decode_tokens - 1       # first token is prefill's
        return n / self.decode_s if self.decode_s > 0 and n > 0 else 0.0

    @property
    def kv_saved_fraction(self) -> float:
        if self.kv_dense == 0:
            return 0.0
        return 1.0 - self.kv_stored / self.kv_dense


def analytic_kv_saved(cfg: ModelConfig) -> float:
    """Compact-store saving at the *configured* keep rate: layer 0 dense +
    keep_prob elsewhere.  The measured per-run figure comes from the decode
    gate log via kv_reuse.storage_saved_fraction."""
    L = max(len(cfg.attention_layers), 1)
    if not (cfg.skip.enabled and cfg.skip.kv_reuse):
        return 0.0
    return 1.0 - (1.0 + (L - 1) * cfg.skip.keep_prob) / L


def _measured_saved_fraction(gates_per_step: List[np.ndarray],
                             cfg: ModelConfig) -> float:
    """Lock-step gate log [L, B] per step -> measured storage saving."""
    if not gates_per_step or not (cfg.skip.enabled and cfg.skip.kv_reuse):
        return 0.0
    g = jnp.asarray(np.stack(gates_per_step, axis=-1))   # [L, B, steps]
    return float(kv_reuse.storage_saved_fraction(g))


class ServeEngine:
    """Lock-step batched engine (baseline; one shared decode position)."""

    def __init__(self, cfg: ModelConfig, params, max_len: int = 512,
                 temperature: float = 0.0):
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.temperature = temperature
        self._decode = jax.jit(partial(model_lib.decode_step, cfg=cfg),
                               donate_argnums=(1,))
        self._prefill = jax.jit(partial(model_lib.prefill, cfg=cfg,
                                        pad_to=max_len))

    def generate(self, prompts: np.ndarray, max_new_tokens: int,
                 rng: Optional[jax.Array] = None) -> Dict[str, np.ndarray]:
        """prompts: [B, T0] int32 (right-aligned, no padding support needed
        for the synthetic workloads).  Returns tokens + stats."""
        cfg = self.cfg
        B, T0 = prompts.shape
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        stats = ServeStats()

        t0 = perf_counter()
        logits, cache, pstats = self._prefill(self.params,
                                              {"tokens": jnp.asarray(prompts)})
        jax.block_until_ready(logits)
        stats.prefill_s = perf_counter() - t0
        stats.prefill_tokens = B * T0

        out = np.zeros((B, max_new_tokens), np.int32)
        keep_acc, keep_n = 0.0, 0
        gates_per_step: List[np.ndarray] = []
        emitted = 0
        tok = sample(logits, rng, self.temperature)
        t0 = perf_counter()
        for i in range(max_new_tokens):
            out[:, i] = np.asarray(tok)
            emitted += B
            pos = T0 + i
            if pos >= self.max_len:
                break
            logits, cache, dstats = self._decode(
                self.params, cache, {"tokens": tok[:, None]},
                jnp.int32(pos))
            if "attn_gate" in dstats:
                gates_per_step.append(
                    np.asarray(dstats["attn_gate"], np.float32))
            keep_acc += float(dstats["keep_frac_sum"])
            keep_n += max(float(dstats["n_routed"]), 1.0)
            rng, sub = jax.random.split(rng)
            tok = sample(logits, sub, self.temperature)
        jax.block_until_ready(logits)
        stats.decode_s = perf_counter() - t0
        stats.decode_tokens = emitted           # tokens actually emitted

        stats.attn_keep_frac = keep_acc / max(keep_n, 1.0)
        stats.kv_saved_fraction = _measured_saved_fraction(gates_per_step, cfg)
        stats.kv_saved_analytic = analytic_kv_saved(cfg)
        return {"tokens": out, "stats": stats}


# ---------------------------------------------------------------------------
# Slot-pool plumbing
# ---------------------------------------------------------------------------

def init_pool(cfg: ModelConfig, max_slots: int, max_len: int) -> Dict:
    """The continuous engine's KV pool: ``max_slots`` cache rows allocated
    once (the paper's fixed on-chip KV history buffer analogue)."""
    return model_lib.init_decode_cache(cfg, max_slots, max_len)

def _align_kv_row(row: jnp.ndarray, target_shape, kind: str,
                  cfg: ModelConfig) -> jnp.ndarray:
    """Reshape one prefill k/v cache row (``[.., T, Hkv, dh]``, padded to
    max_len) to the pool's layout for its layer kind: head-major transpose
    for ``bhtd`` pools, truncation to the ring extent for window layers
    (positions < W: ring slot s ≡ position s, so the prefix IS the ring)."""
    if kind == LOCAL and cfg.window_size:
        W = target_shape[-3]
        if row.shape[-3] != W:
            row = jax.lax.slice_in_dim(row, 0, W, axis=row.ndim - 3)
    elif cfg.kv_cache_layout == "bhtd":
        row = row.swapaxes(-3, -2)           # prefill collects [.., T, H, d]
    return row


def pool_insert(pool: Dict, cache: Dict, slot, cfg: ModelConfig) -> Dict:
    """Scatter a single-request prefill cache (batch dim 1, KV padded to
    max_len) into row ``slot`` of the pool.  ``slot`` may be traced — the
    engine runs this jitted (donating the pool) so admission is one fused
    scatter, not an eager op per cache leaf."""
    def one(path, pl, nl):
        names = [getattr(p, "key", "") for p in path]
        stage_leaf = names[0] == "stages"
        row = jnp.take(nl, 0, axis=1 if stage_leaf else 0)
        if names[-1] in ("k", "v"):
            kind = cfg.block_kind(int(names[-2][3:]))
            tgt = pl.shape[2:] if stage_leaf else pl.shape[1:]
            if stage_leaf:
                tgt = (row.shape[0],) + tuple(tgt)
            row = _align_kv_row(row, tgt, kind, cfg)
        row = row.astype(pl.dtype)
        return pl.at[:, slot].set(row) if stage_leaf else pl.at[slot].set(row)

    return jax.tree_util.tree_map_with_path(one, pool, cache)


@dataclasses.dataclass
class _RunState:
    """Host-side state of one ``run()``, shared by the dense and paged
    loops (the consolidation of the per-loop ``finish``/``preempt``
    closures the PR-2 review flagged).  ``metrics`` is the run's
    source-of-truth registry — ``stats`` counter fields are derived from
    it at ``_finalize``."""
    stats: ServeStats
    results: Dict[int, RequestResult]
    t_run: float
    rng: jax.Array
    metrics: MetricsRegistry = dataclasses.field(
        default_factory=MetricsRegistry)
    keep_acc: float = 0.0
    keep_n: float = 0.0
    # -- observability bookkeeping -----------------------------------------
    step_idx: int = 0                     # cumulative inner decode steps
    disp_idx: int = 0                     # decode dispatches (epoch index)
    compiled_seen: int = 0                # jit cache size at run start
    traced: set = dataclasses.field(default_factory=set)     # request spans
    admitted: set = dataclasses.field(default_factory=set)   # prefill spans
    # paged-mode extras
    hist: Optional[history_mod.HistoryAccounting] = None
    # crash consistency: last boundary a snapshot was published at
    last_snap: int = -1
    # adaptive degradation (paged fused mode): cross-epoch decode_steps
    # cap remembered after a page-pressure shrink (0 = uncapped), and the
    # clean-epoch streak that grows it back (hysteresis)
    epoch_cap: int = 0
    clean_epochs: int = 0
    # chunked-prefill staging (at most one prompt in flight at a time)
    stage_cache: Optional[Dict] = None
    stage_gates: List[np.ndarray] = dataclasses.field(default_factory=list)
    # fused-epoch mode: first tokens sampled inside a prefill dispatch
    # whose values the host has not yet synced ({slot: device [1] int32});
    # the decode loop reads them straight off the device carry
    pending: Dict[int, object] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class _WarmAdmission:
    """Host state of one warm-prefix admission between the scheduler's
    probe (allocator work done, device work deferred) and the slot's
    first prefill chunk (COW copy + staging-cache reconstruction)."""
    rec: prefix_mod.PrefixRecord
    # boundary-page COW: (src shared page, dst private page, entries kept);
    # None when the shared prefix ends exactly on a page boundary
    copy: Optional[tuple] = None


class RequestHandle(int):
    """What ``submit()`` returns: the request uid (is-an ``int``, so every
    pre-streaming caller that compared / stored uids keeps working) plus
    the streaming surface.

    ``tokens()`` yields ``(token, step)`` pairs as the engine emits them,
    *driving the engine itself* when the buffer runs dry — iterating a
    handle interleaves engine iterations with consumption, no thread
    needed.  Emission granularity is the engine iteration (= epoch in
    fused mode): see docs/serving.md for the exact contract.
    """

    def __new__(cls, uid: int, engine):
        h = super().__new__(cls, uid)
        h.engine = engine
        return h

    @property
    def uid(self) -> int:
        return int(self)

    def done(self) -> bool:
        """True once the request has a final :class:`RequestResult`."""
        return int(self) in self.engine._stream_done

    def result(self) -> Optional["RequestResult"]:
        """The final result, or None while the request is still running
        (``tokens()`` / ``run()`` drive it to completion)."""
        return self.engine._stream_results.get(int(self))

    def tokens(self):
        """Iterate ``(token, step)`` pairs for this request, pumping the
        engine's run loop whenever no buffered token is ready."""
        return self.engine._stream_tokens(int(self))


class ContinuousBatchingEngine:
    """Continuous batching over a fixed slot pool (per-sequence positions).

    Requests are admitted into free KV slots, prefilled (length-bucketed
    where exact, or chunk-by-chunk with ``prefill_chunk > 0``), decoded
    concurrently — each sequence at its own position ``t[slot]`` — and
    evicted on stop-token / length, freeing the slot for the next queued
    request.  Both run loops consume ``Scheduler.plan_step`` plans: each
    engine iteration executes at most one prefill work unit alongside one
    ragged decode step over every resident slot, so with chunking on a
    long prompt can no longer stall resident decodes for its whole length
    (head-of-line blocking — see docs/serving.md).

    Constructor levers:
      max_slots / max_len  — KV pool geometry (slots × positions).
      temperature          — 0.0 = greedy sampling.
      prefill_buckets      — monolithic-prefill padding buckets (defaulted
                             when exact; unused once chunking is on).
      kv_mode              — "dense" slot pool or "paged" entry stream.
      page_size/num_pages  — paged-pool geometry.
      prefill_chunk        — chunk size in tokens; None defers to
                             ``cfg.prefill_chunk``; 0 = monolithic
                             (parity default).
      decode_steps         — decode iterations fused into one jitted
                             device-resident dispatch (``model.decode_loop``
                             / ``model.paged_decode_loop``); None defers to
                             ``cfg.decode_steps_per_dispatch``; 1 = the
                             single-step loops (parity default).  With
                             N > 1 sampling, stop/length detection and
                             position advance run on device, the host
                             syncs once per epoch, and its scheduling
                             work overlaps the in-flight dispatch — see
                             docs/serving.md.  Token output is identical
                             to N = 1 at temperature 0.
      spec_k               — self-speculative decoding (docs/
                             speculative.md): each decode iteration
                             drafts up to ``spec_k`` tokens per resident
                             with an aggressively-skipped forward, then
                             verifies the whole window in ONE chunked
                             dispatch — two dispatches emit up to
                             ``spec_k + 1`` tokens per slot.  0 = off
                             (parity default).  Requires
                             ``can_speculate(cfg)`` and is mutually
                             exclusive with ``decode_steps > 1`` (both
                             amortize host overhead over multi-token
                             dispatches).  Token output is identical to
                             plain decoding at temperature 0; at
                             temperature > 0 the per-token emission
                             distribution is preserved exactly
                             (speculative-sampling identity).
      draft_keep           — draft-pass router keep-rate override in
                             (0, 1]; values < 1 bias every router toward
                             skipping during the draft loop only (the
                             verify pass always runs the full model).
                             None/1.0 = draft with the configured
                             routing (self-drafting, acceptance-
                             friendly).
      step_tokens          — optional per-step token budget for
                             ``plan_step`` (decode slots cost 1 each, a
                             chunk its length); None = unbudgeted.
      trace                — observability: ``None`` (default, off — a
                             no-op ``NullTracer``), a ``repro.obs.Tracer``
                             to record into, or a path string — the
                             engine then builds a tracer and writes the
                             Chrome-trace JSON there at the end of every
                             ``run()`` (perfetto-loadable; span taxonomy
                             in docs/observability.md).  Independent of
                             tracing, every run fills a
                             ``MetricsRegistry`` returned as
                             ``run()['metrics']``.
      mesh                 — optional ``jax.sharding.Mesh`` with a
                             ``model`` axis: tensor-parallel sharded
                             serving.  Params are re-sharded under the
                             serve-mode ``ShardingPolicy`` (head-sharded
                             attention, column/row-split MLP) and the KV
                             slot pool / paged store is head-sharded over
                             ``model`` via ``ShardingPolicy.cache_specs``;
                             every jitted step carries explicit in/out
                             shardings.  Block tables, free list and the
                             scheduler stay host-side and replicated, so
                             engine semantics (and its token output) are
                             unchanged — see docs/distributed.md.
      sharding_policy      — optional pre-built serve-mode policy (defaults
                             to ``ShardingPolicy(mesh, cfg, mode="serve")``).

    Robustness levers (docs/robustness.md):
      faults               — a ``serve.faults.FaultPlan`` (or list of
                             ``Fault``) of scheduled injections consumed
                             at the engine's seams; None = no faults.
      watchdog             — a ``serve.faults.Watchdog``: per-dispatch
                             wall-time monitor; a sync past its hard
                             timeout raises ``HungDispatch`` with the
                             flushed trace path attached.
      snapshot_dir         — directory for crash-consistent boundary
                             snapshots (None = off); ``snapshot_every``
                             sets the cadence in engine iterations.
                             ``resume()`` restores the newest snapshot.
      max_queue_depth /    — load shedding: ``submit()`` raises
      max_queue_delay_s      ``AdmissionRejected`` when the queue is this
                             deep, or when the queue head has already
                             waited past the delay bound (the request
                             would only be joining a queue that is
                             already falling behind).
      max_preemptions      — retry budget: a request preempted more than
                             this many times finishes with reason
                             "preempt_budget" (partial tokens) instead of
                             requeueing forever; None = unlimited.
    """

    def __init__(self, cfg: ModelConfig, params, max_slots=_UNSET,
                 max_len=_UNSET, temperature=_UNSET,
                 prefill_buckets=_UNSET,
                 kv_mode=_UNSET, page_size=_UNSET,
                 num_pages=_UNSET,
                 prefill_chunk=_UNSET,
                 decode_steps=_UNSET,
                 spec_k=_UNSET,
                 draft_keep=_UNSET,
                 step_tokens=_UNSET,
                 trace=_UNSET,
                 mesh=_UNSET, sharding_policy=_UNSET,
                 faults=_UNSET, watchdog=_UNSET,
                 snapshot_dir=_UNSET,
                 snapshot_every=_UNSET,
                 max_queue_depth=_UNSET,
                 max_queue_delay_s=_UNSET,
                 max_preemptions=_UNSET,
                 kv_dtype=_UNSET, prefix_cache=_UNSET, prefix_block=_UNSET,
                 *, config: Optional[EngineConfig] = None):
        # -- deprecation shim: explicit flat kwargs -> EngineConfig --------
        legacy = {name: value for name, value in (
            ("max_slots", max_slots), ("max_len", max_len),
            ("temperature", temperature),
            ("prefill_buckets", prefill_buckets), ("kv_mode", kv_mode),
            ("page_size", page_size), ("num_pages", num_pages),
            ("prefill_chunk", prefill_chunk), ("decode_steps", decode_steps),
            ("spec_k", spec_k), ("draft_keep", draft_keep),
            ("step_tokens", step_tokens), ("trace", trace), ("mesh", mesh),
            ("sharding_policy", sharding_policy), ("faults", faults),
            ("watchdog", watchdog), ("snapshot_dir", snapshot_dir),
            ("snapshot_every", snapshot_every),
            ("max_queue_depth", max_queue_depth),
            ("max_queue_delay_s", max_queue_delay_s),
            ("max_preemptions", max_preemptions), ("kv_dtype", kv_dtype),
            ("prefix_cache", prefix_cache), ("prefix_block", prefix_block),
        ) if value is not _UNSET}
        if legacy:
            if config is not None:
                raise ConfigError(
                    "pass either config=EngineConfig(...) or the legacy "
                    "flat kwargs, not both (got config= plus "
                    f"{sorted(legacy)})")
            _warn_legacy_kwargs(sorted(legacy))
            config = EngineConfig.from_kwargs(**legacy)
        elif config is None:
            config = EngineConfig()
        self.config = config
        kvc, sch = config.kv, config.scheduling
        spc, rob, obs = config.spec, config.robustness, config.obs
        max_slots, max_len = sch.max_slots, sch.max_len
        temperature = config.temperature
        prefill_buckets, prefill_chunk = sch.prefill_buckets, sch.prefill_chunk
        decode_steps, step_tokens = sch.decode_steps, sch.step_tokens
        kv_mode, page_size = kvc.kv_mode, kvc.page_size
        num_pages = kvc.num_pages
        spec_k, draft_keep = spc.spec_k, spc.draft_keep
        trace, mesh = obs.trace, obs.mesh
        sharding_policy = obs.sharding_policy
        faults, watchdog = rob.faults, rob.watchdog
        snapshot_dir, snapshot_every = rob.snapshot_dir, rob.snapshot_every
        max_queue_depth = rob.max_queue_depth
        max_queue_delay_s = rob.max_queue_delay_s
        max_preemptions = rob.max_preemptions
        self.cfg = cfg
        self.tracer = as_tracer(trace)
        self.metrics: Optional[MetricsRegistry] = None   # last run's registry
        self._jitted: List = []          # every jitted step (compile probe)
        # the tiles each traced fused-linear call shape ran with, keyed by
        # ("MxKxN", jitted step name); filled as the steps trace
        self._linear_plans: Dict[Tuple[str, str],
                                 fused_linear_mod.FusedLinearPlan] = {}
        self.mesh = mesh
        self.policy: Optional[ShardingPolicy] = None
        self._param_sh = self._repl = None
        if mesh is not None:
            if cfg.frontend != "token":
                raise ValueError("sharded serving requires a token frontend")
            if cfg.use_kernels and jax.default_backend() != "cpu":
                # off the CPU a Pallas kernel is one Mosaic custom call,
                # which the SPMD partitioner cannot split
                raise ConfigError("sharded serving runs the jnp path: "
                                  "Mosaic kernels cannot be partitioned "
                                  "automatically (use_kernels=False)")
            pol = sharding_policy or ShardingPolicy(mesh, cfg, mode="serve")
            if pol.mode != "serve":
                raise ValueError("ContinuousBatchingEngine requires a "
                                 "serve-mode ShardingPolicy")
            self.policy = pol
            self._repl = NamedSharding(mesh, P())
            self._param_sh = pol.param_specs(params)
            # weight-stationary re-shard onto the serve mesh (column-split
            # merged wqkv / [gate|up] with the GQA row-parallel fallback —
            # the PR-3 merged-tree rules)
            params = jax.device_put(params, self._param_sh)
        self.params = params
        self.max_slots = max_slots
        self.max_len = max_len
        self.temperature = temperature
        if kv_mode not in ("dense", "paged"):
            raise ValueError(f"unknown kv_mode {kv_mode!r}")
        if kv_mode == "paged" and not paged_mod.can_page(cfg):
            raise ValueError(
                f"{cfg.name}: paged KV requires an all-global-attention "
                "stack with masked-mode routing — use kv_mode='dense'")
        self.kv_mode = kv_mode
        self.prefill_chunk = int(cfg.prefill_chunk if prefill_chunk is None
                                 else prefill_chunk)
        if self.prefill_chunk < 0:
            raise ValueError("prefill_chunk must be >= 0 (0 = monolithic)")
        if self.prefill_chunk and not can_chunk_prefill(cfg):
            raise ValueError(
                f"{cfg.name}: chunked prefill requires an all-global-"
                "attention stack with masked-mode routing (resumable "
                "cache state) — use prefill_chunk=0")
        self.decode_steps = int(cfg.decode_steps_per_dispatch
                                if decode_steps is None else decode_steps)
        if self.decode_steps < 1:
            raise ValueError("decode_steps must be >= 1 (1 = single-step)")
        self.spec_k = int(spec_k)
        if self.spec_k < 0:
            raise ValueError("spec_k must be >= 0 (0 = off)")
        self.draft_keep = 1.0 if draft_keep is None else float(draft_keep)
        # test hook: callable (uid, drafts [k] int32) -> [k] replacing a
        # slot's draft proposals before verification (forces a host sync
        # of the draft tokens — test-only, not a serving lever)
        self.draft_override = None
        self.draft_params = params
        if self.spec_k:
            if not can_speculate(cfg):
                raise ValueError(
                    f"{cfg.name}: speculative decoding reuses the chunked-"
                    "prefill stack pass for verification — it requires an "
                    "all-global-attention stack with masked-mode routing "
                    "and the bthd cache layout (spec_k=0)")
            if self.decode_steps > 1:
                raise ValueError(
                    "spec_k and decode_steps > 1 are mutually exclusive — "
                    "both amortize host overhead over multi-token "
                    "dispatches; pick one")
            if not 0.0 < self.draft_keep <= 1.0:
                raise ValueError("draft_keep must be in (0, 1]")
            self.draft_params = draft_router_bias(params, self.draft_keep)
        self.step_tokens = step_tokens
        if prefill_buckets is not None and not can_bucket(cfg):
            raise ValueError(
                f"{cfg.name}: prefill bucketing pads prompts, which corrupts "
                "ring-buffer/SSM state and gather-mode capacity — this "
                "config requires exact-length prefill (prefill_buckets=None)")
        if (prefill_buckets is None and can_bucket(cfg)
                and not self.prefill_chunk):
            # chunked prefill quantizes shapes to the chunk size itself;
            # buckets only serve the monolithic path
            prefill_buckets = default_buckets(max_len)
        self.scheduler = Scheduler(max_slots, max_len,
                                   buckets=prefill_buckets,
                                   prefill_chunk=self.prefill_chunk)

        # -- jitted steps, with explicit in/out shardings under a policy ----
        # (``last_index`` is threaded positionally through thin wrappers:
        # pjit rejects kwargs once in_shardings are pinned)
        pol = self.policy
        rep = self._repl if pol is not None else None
        _jit = self._jit_step

        self._pool_sh = self._pcache_sh = None
        if pol is not None:
            self._pool_sh = pol.cache_specs(
                jax.eval_shape(partial(init_pool, cfg, max_slots, max_len)),
                layout=cfg.kv_cache_layout)
            self._warn_if_unsharded(self._pool_sh, "KV slot pool")
            # prefill collects time-major rows regardless of the pool
            # layout; the serve head-axis rule is layout-independent.
            # seq_fallback=False: these single-request caches are built at
            # *bucketed* lengths the max_len-derived spec tree must cover,
            # so a non-dividing head axis replicates rather than riding a
            # time split that some bucket wouldn't divide.
            self._pcache_sh = pol.cache_specs(
                jax.eval_shape(
                    lambda p: model_lib.prefill(
                        p, {"tokens": jnp.zeros((1, max_len), jnp.int32)},
                        cfg=cfg, pad_to=max_len)[1],
                    params),
                layout="bthd", seq_fallback=False)

        # first-token sampling is folded INTO the prefill dispatch (the
        # rng key rides along), so the completion path has no eager
        # sample and — in fused mode — no host sync at all
        def _prefill_fn(p, batch, last_index, rng):
            logits, cache, stats = model_lib.prefill(
                p, batch, cfg=cfg, pad_to=max_len, last_index=last_index)
            return sample(logits, rng, temperature), cache, stats

        self._decode = _jit(
            partial(model_lib.decode_step, cfg=cfg), donate=(1,),
            in_sh=(self._param_sh, self._pool_sh, rep, rep),
            out_sh=(rep, self._pool_sh, rep))
        self._prefill = _jit(
            _prefill_fn,
            in_sh=(self._param_sh, rep, rep, rep),
            out_sh=(rep, self._pcache_sh, rep))
        # chunked completions sample from the last chunk's logits in a
        # (tiny) jitted dispatch of their own
        self._sample_tok = _jit(
            lambda logits, rng: sample(logits, rng, temperature),
            in_sh=(rep, rep), out_sh=rep)
        # fused decode loops, compiled lazily per power-of-two epoch length
        self._dense_loops: Dict[int, object] = {}
        self._paged_loops: Dict[int, object] = {}
        # speculative draft loops (lazy per draft length) + the verify /
        # commit steps (single jits — their window width is shape-driven)
        self._spec_drafts: Dict[int, object] = {}
        self._spec_verify_fn = None
        self._spec_commit_fn = None
        self._spec_vc_fn = None
        self._insert = _jit(
            partial(pool_insert, cfg=cfg), donate=(0,),
            in_sh=(self._pool_sh, self._pcache_sh, rep),
            out_sh=self._pool_sh)
        if self.prefill_chunk:
            # staging cache capacity: max_len rounded up to a chunk
            # multiple, so the right-padded final chunk always fits
            C = self.prefill_chunk
            self._chunk_cap = -(-max_len // C) * C
            self._chunk_sh = None
            if pol is not None:
                self._chunk_sh = pol.cache_specs(
                    jax.eval_shape(partial(model_lib.init_chunk_cache,
                                           cfg, 1, self._chunk_cap)),
                    layout="bthd", seq_fallback=False)

            def _chunk_fn(p, cache, batch, t0, last_index):
                return model_lib.prefill_chunk(p, cache, batch, t0, cfg=cfg,
                                               last_index=last_index)

            self._chunk_step = _jit(
                _chunk_fn, donate=(1,),
                in_sh=(self._param_sh, self._chunk_sh, rep, rep, rep),
                out_sh=(rep, self._chunk_sh, rep))

            def _ins_staged(pool, cache, slot):
                return pool_insert(
                    pool, model_lib.slice_cache_time(cache, max_len),
                    slot, cfg)

            self._insert_staged = _jit(
                _ins_staged, donate=(0,),
                in_sh=(self._pool_sh, self._chunk_sh, rep),
                out_sh=self._pool_sh)
        self.kv_dtype = kvc.kv_dtype
        self.prefix: Optional[prefix_mod.PrefixCache] = None
        # persistent device page store (paged mode): stashed by the run
        # loops at clean exit so prefix records stay backed across runs
        self._store = None
        if kv_mode == "paged":
            self.n_attn = paged_mod.num_attention_layers(cfg)
            self.page_size = page_size
            # default pool: the dense pool's worst case (every token fresh
            # at every layer) — alloc-on-demand still keeps the *live*
            # footprint far below it; size it down to see backpressure.
            cap = max_len * self.n_attn
            self.num_pages = (num_pages if num_pages is not None
                              else max_slots * -(-cap // page_size))
            self.allocator = paged_mod.PageAllocator(
                self.num_pages, page_size, max_slots,
                slot_entry_capacity=cap)
            self._store_sh = None
            if pol is not None:
                self._store_sh = pol.cache_specs(jax.eval_shape(
                    partial(paged_mod.init_store, cfg, self.num_pages,
                            self.page_size, kv_dtype=self.kv_dtype)))
                self._warn_if_unsharded(self._store_sh, "paged KV store")

            def _prefill_paged_fn(p, batch, last_index, rng):
                logits, cache, stats = model_lib.prefill(
                    p, batch, cfg=cfg, last_index=last_index)
                return sample(logits, rng, temperature), cache, stats

            # paged prefill keeps the exact (bucketed) length — pages
            # replace the pool's max_len padding.  The spec tree from the
            # padded prefill cache applies unchanged (specs are
            # shape-independent; the head axis is identical).
            self._prefill_paged = _jit(
                _prefill_paged_fn,
                in_sh=(self._param_sh, rep, rep, rep),
                out_sh=(rep, self._pcache_sh, rep))
            pack_cache_sh = (self._chunk_sh if self.prefill_chunk
                             else self._pcache_sh)
            kv_dt = self.kv_dtype

            def _pack_fn(store, cache, gates, valid_len, bt_row,
                         start_token, start_entry):
                return paged_mod.pack_prefill(
                    store, cache, gates, valid_len, bt_row, cfg,
                    start_token=start_token, start_entry=start_entry,
                    kv_dtype=kv_dt)

            self._pack = _jit(
                _pack_fn, donate=(0,),
                in_sh=(self._store_sh, pack_cache_sh, rep, rep, rep,
                       rep, rep),
                out_sh=self._store_sh)
            self._decode_paged = _jit(
                partial(model_lib.paged_decode_step, cfg=cfg), donate=(1,),
                in_sh=(self._param_sh, self._store_sh, rep, rep, rep, rep),
                out_sh=(rep, self._store_sh, rep))
            if kvc.prefix_cache:
                if not can_chunk_prefill(cfg):
                    raise ConfigError(
                        f"{cfg.name}: prefix_cache resumes prefill from a "
                        "reconstructed staging cache — it requires the "
                        "chunk-resumable stack chunked prefill needs")
                self.prefix = prefix_mod.PrefixCache(
                    self.allocator, block=kvc.prefix_block,
                    reuse=paged_mod.reuse_enabled(cfg),
                    max_records=kvc.prefix_max_records)
                self.scheduler.prefix_probe = self._prefix_probe
                # in-flight warm admissions: slot -> _WarmAdmission
                self._warm_pending: Dict[int, _WarmAdmission] = {}
                # warm-suffix forward runs chunk-style even under
                # monolithic prefill: the suffix resumes mid-sequence, so
                # it needs the resumable staging-cache step.  Its cache
                # capacity covers the whole prompt region.
                self._warm_cap = (self._chunk_cap if self.prefill_chunk
                                  else max_len)
                self._warm_sh = None
                if pol is not None:
                    self._warm_sh = (
                        self._chunk_sh if self.prefill_chunk
                        else pol.cache_specs(
                            jax.eval_shape(partial(
                                model_lib.init_chunk_cache, cfg, 1,
                                self._warm_cap)),
                            layout="bthd", seq_fallback=False))
                warm_cap = self._warm_cap
                kv_dt = self.kv_dtype

                def _warm_fn(store, bt_row, fill):
                    kv_v, vv_v = paged_mod.views_from_pages(
                        store, bt_row, fill, cfg, warm_cap,
                        kv_dtype=kv_dt)
                    return paged_mod.chunk_cache_from_views(kv_v, vv_v, cfg)

                # shared-prefix entries -> batch-1 staging cache (the
                # exact inverse of pack_prefill; docs/kvcache.md)
                self._warm_cache = _jit(
                    _warm_fn, in_sh=(self._store_sh, rep, rep),
                    out_sh=self._warm_sh)
                self._cow_copy = _jit(
                    paged_mod.copy_page_masked, donate=(0,),
                    in_sh=(self._store_sh, rep, rep, rep),
                    out_sh=self._store_sh)
                if self.prefill_chunk:
                    self._warm_chunk_step = self._chunk_step
                else:
                    def _warm_chunk_fn(p, cache, batch, t0, last_index):
                        return model_lib.prefill_chunk(
                            p, cache, batch, t0, cfg=cfg,
                            last_index=last_index)

                    self._warm_chunk_step = _jit(
                        _warm_chunk_fn, donate=(1,),
                        in_sh=(self._param_sh, self._warm_sh, rep, rep,
                               rep),
                        out_sh=(rep, self._warm_sh, rep))
        self._uid = 0
        # -- streaming surface (docs/serving.md) ----------------------------
        self._streams: Dict[int, List] = {}      # uid -> [(token, step), ..]
        self._stream_pos: Dict[int, int] = {}    # uid -> emitted high-water
        self._stream_done: set = set()           # uids with a final result
        self._stream_results: Dict[int, RequestResult] = {}
        self._driver = None                      # active run-loop generator
        self._driver_rng = None
        self._driver_out: Optional[Dict] = None
        # -- robustness state (docs/robustness.md) --------------------------
        self.faults = as_fault_plan(faults)
        self.watchdog = watchdog
        self.snapshot_dir = snapshot_dir
        self.snapshot_every = max(1, int(snapshot_every))
        self.max_queue_depth = max_queue_depth
        self.max_queue_delay_s = max_queue_delay_s
        self.max_preemptions = max_preemptions
        self._cancelled: set = set()     # uids awaiting cooperative cancel
        self._shed_pending: List[str] = []   # shed reasons since last run
        self._resume = None              # (device_tree, host, step) to apply

    # -- jit plumbing ------------------------------------------------------
    def _jit_step(self, fn, donate=(), in_sh=None, out_sh=None):
        """jit with explicit in/out shardings under a mesh policy (pjit
        rejects kwargs once shardings are pinned, so callers thread every
        argument positionally).  Every jitted step is registered so the
        run loops can poll total compile-cache growth (the recompile
        counter).  Each fused-linear call the step traces records its
        tile plan under the step's name."""
        plans = self._linear_plans
        name = getattr(fn, "func", fn).__name__       # partials too
        phase = name.strip("_")

        @wraps(fn)
        def traced(*args, **kwargs):
            with fused_linear_mod.recording(plans, phase):
                return fn(*args, **kwargs)

        traced.__name__ = name          # the program keeps the step's name
        if self.policy is None:
            jitted = jax.jit(traced, donate_argnums=donate,
                             compiler_options=COMPILER_OPTIONS)
        else:
            jitted = jax.jit(traced, donate_argnums=donate,
                             in_shardings=in_sh, out_shardings=out_sh,
                             compiler_options=COMPILER_OPTIONS)
        self._jitted.append(jitted)
        return jitted

    def _dense_loop(self, n: int):
        """The jitted N-step dense decode loop (``model.decode_loop``),
        compiled once per epoch length; the pool rides the scan carry and
        is donated, so the cache updates in place across all N steps."""
        fn = self._dense_loops.get(n)
        if fn is None:
            cfg, max_len, temp = self.cfg, self.max_len, self.temperature

            def loop_fn(p, pool, feed, t, active, budget, stop, rng):
                return model_lib.decode_loop(
                    p, pool, feed, t, active, budget, stop, rng,
                    n_steps=n, cfg=cfg, max_len=max_len, temperature=temp)

            rep = self._repl
            fn = self._jit_step(
                loop_fn, donate=(1,),
                in_sh=(self._param_sh, self._pool_sh) + (rep,) * 6,
                out_sh=(self._pool_sh, rep))
            self._dense_loops[n] = fn
        return fn

    def _paged_loop(self, n: int):
        """``_dense_loop``'s paged twin (``model.paged_decode_loop``):
        the entry-stream fill advances on device, so the allocator replay
        happens once per epoch from the returned gate log."""
        fn = self._paged_loops.get(n)
        if fn is None:
            cfg, max_len, temp = self.cfg, self.max_len, self.temperature

            def loop_fn(p, store, feed, t, fill, active, budget, stop,
                        rng, block_table):
                return model_lib.paged_decode_loop(
                    p, store, feed, t, fill, active, budget, stop, rng,
                    block_table, n_steps=n, cfg=cfg, max_len=max_len,
                    temperature=temp)

            rep = self._repl
            fn = self._jit_step(
                loop_fn, donate=(1,),
                in_sh=(self._param_sh, self._store_sh) + (rep,) * 8,
                out_sh=(self._store_sh, rep))
            self._paged_loops[n] = fn
        return fn

    def _spec_draft(self, n: int):
        """The jitted n-step speculative draft loop for the engine's KV
        mode, compiled lazily per draft length (n <= spec_k, a handful
        of variants).  The pool/store is donated: draft KV writes are
        tentative — dense verify overwrites the window rows outright,
        and the paged verifier masks the tentative entries out before
        ``commit_verified`` rewrites them."""
        fn = self._spec_drafts.get(n)
        if fn is None:
            cfg, temp = self.cfg, self.temperature
            rep = self._repl
            if self.kv_mode == "paged":
                def draft_fn(p, store, feed, t, fill, active, rng, bt):
                    return model_lib.paged_draft_loop(
                        p, store, feed, t, fill, active, rng, bt,
                        n_steps=n, cfg=cfg, temperature=temp)

                fn = self._jit_step(
                    draft_fn, donate=(1,),
                    in_sh=(self._param_sh, self._store_sh) + (rep,) * 6,
                    out_sh=(self._store_sh, rep))
            else:
                def draft_fn(p, pool, feed, t, rng):
                    return model_lib.draft_loop(
                        p, pool, feed, t, rng, n_steps=n, cfg=cfg,
                        temperature=temp)

                fn = self._jit_step(
                    draft_fn, donate=(1,),
                    in_sh=(self._param_sh, self._pool_sh) + (rep,) * 3,
                    out_sh=(self._pool_sh, rep))
            self._spec_drafts[n] = fn
        return fn

    def _spec_verify(self):
        """The jitted verify step (the window width C is shape-driven,
        so one jit covers every draft length).  Dense mode donates the
        pool — the verifier's window rows ARE the committed state; paged
        mode reads the store without donating, since commit happens in
        the separate ``_spec_commit`` dispatch once the host knows each
        slot's accepted prefix.  The per-column argmax is computed on
        device so the temperature-0 sync never pulls [S, C, V] logits."""
        fn = self._spec_verify_fn
        if fn is None:
            cfg = self.cfg
            rep = self._repl
            if self.kv_mode == "paged":
                def vfn(p, store, batch, t0, bt, fill):
                    logits, stats = model_lib.paged_verify_chunk(
                        p, store, batch, t0, bt, fill, cfg=cfg)
                    return (jnp.argmax(logits, -1).astype(jnp.int32),
                            logits, stats)

                fn = self._jit_step(
                    vfn,
                    in_sh=(self._param_sh, self._store_sh) + (rep,) * 4,
                    out_sh=(rep, rep, rep))
            else:
                def vfn(p, pool, batch, t0):
                    logits, pool, stats = model_lib.verify_chunk(
                        p, pool, batch, t0, cfg=cfg)
                    return (jnp.argmax(logits, -1).astype(jnp.int32),
                            logits, pool, stats)

                fn = self._jit_step(
                    vfn, donate=(1,),
                    in_sh=(self._param_sh, self._pool_sh, rep, rep),
                    out_sh=(rep, rep, self._pool_sh, rep))
            self._spec_verify_fn = fn
        return fn

    def _spec_commit(self):
        """Paged tentative-commit (``model.commit_verified``): rewrite
        the entry stream from the pre-window fill with the verifier's KV
        for exactly the emitted columns — the device half of the
        rollback protocol (the host half is allocator replay + trim)."""
        fn = self._spec_commit_fn
        if fn is None:
            cfg = self.cfg
            rep = self._repl

            def cfn(store, bk, bv, gates, t0, bt, fill0, committed,
                    active):
                return model_lib.commit_verified(
                    store, bk, bv, gates, t0, bt, fill0, committed,
                    active, cfg=cfg)

            fn = self._jit_step(
                cfn, donate=(0,),
                in_sh=(self._store_sh,) + (rep,) * 8,
                out_sh=(self._store_sh, rep))
            self._spec_commit_fn = fn
        return fn

    def _spec_verify_commit(self):
        """Fused paged verify + greedy accept + tentative-commit: ONE
        dispatch where the two-phase path (``_spec_verify`` sync, host
        accept, ``_spec_commit`` dispatch) takes two — the greedy accept
        rule and ``_plan_emission``'s truncation (stop token, generation
        budget, ``max_len``) are pure elementwise arithmetic over the
        verifier's argmax chain, so at temperature 0 the device can
        decide the committed column count itself and rewrite the entry
        stream without waiting on the host.  The host still replays the
        acceptance from the synced argmax chain for bookkeeping and
        asserts it agrees (``_run_paged_spec``).  Temperature > 0 keeps
        the two-dispatch path: exact accept/resample needs host-side
        float64 probability arithmetic."""
        fn = self._spec_vc_fn
        if fn is None:
            cfg = self.cfg
            rep = self._repl

            def vcfn(p, store, batch, t0, bt, fill0, active, budget_cap,
                     len_cap, stop_tok):
                logits, stats = model_lib.paged_verify_chunk(
                    p, store, batch, t0, bt, fill0, cfg=cfg)
                tgt = jnp.argmax(logits, -1).astype(jnp.int32)    # [S, C]
                C = tgt.shape[1]
                if C > 1:
                    match = batch["tokens"][:, 1:] == tgt[:, :-1]
                    acc = jnp.where(match.all(axis=1), C - 1,
                                    jnp.argmin(match, axis=1)
                                    ).astype(jnp.int32)
                else:
                    acc = jnp.zeros(tgt.shape[:1], jnp.int32)
                # emitted chain == tgt[:, :acc+1]; truncate exactly as
                # _plan_emission does (stop inclusive, budget, max_len)
                cols = jnp.arange(C, dtype=jnp.int32)[None, :]
                is_stop = ((stop_tok[:, None] >= 0)
                           & (tgt == stop_tok[:, None]))
                stop_n = jnp.min(jnp.where(is_stop, cols, C), axis=1) + 1
                n = jnp.minimum(jnp.minimum(acc + 1, stop_n),
                                jnp.minimum(budget_cap, len_cap))
                committed = jnp.where(active, jnp.maximum(n, 1),
                                      0).astype(jnp.int32)
                bk, bv = stats["kv_token"]
                store2, _ = model_lib.commit_verified(
                    store, bk, bv, stats["attn_gate"], t0, bt, fill0,
                    committed, active, cfg=cfg)
                return store2, tgt, stats["attn_gate"], committed

            fn = self._jit_step(
                vcfn, donate=(1,),
                in_sh=(self._param_sh, self._store_sh) + (rep,) * 8,
                out_sh=(self._store_sh, rep, rep, rep))
            self._spec_vc_fn = fn
        return fn

    # -- sharding sanity ---------------------------------------------------
    def _warn_if_unsharded(self, sh_tree, what: str) -> None:
        """If no leaf of ``sh_tree`` landed on the model axis (head count
        and fallback axes all non-dividing), the structure replicates on
        every device — legal, but the ~1/TP per-chip KV memory the mesh
        was passed for is gone, so say it loudly instead of silently."""
        def axes(sh):
            out = []
            for ax in sh.spec:
                if ax is not None:
                    out.extend(ax if isinstance(ax, tuple) else (ax,))
            return out

        if not any("model" in axes(sh)
                   for sh in jax.tree_util.tree_leaves(sh_tree)):
            warnings.warn(
                f"sharded serving: the {what} has no dimension dividing "
                f"the mesh's model axis (size {self.policy.model_size}) "
                f"and is fully replicated per device — pick a TP degree "
                f"dividing the KV head count (or cache extents) to get "
                f"the ~1/TP per-chip KV footprint", stacklevel=3)

    # -- request intake ----------------------------------------------------
    def submit(self, tokens: np.ndarray, max_new_tokens: int,
               stop_token: Optional[int] = None,
               deadline_s: Optional[float] = None) -> "RequestHandle":
        """Queue one prompt; returns its :class:`RequestHandle` (an
        ``int`` subclass carrying the uid, so callers that treated the
        return value as a plain uid are unaffected).  Iterating
        ``handle.tokens()`` streams ``(token, step)`` pairs and drives
        the engine's run loop on demand; ``run()`` remains the drain-
        everything entry point.

        ``deadline_s`` is a wall-clock budget measured from submission:
        past it the request finishes with ``finish_reason == "deadline"``
        (partial tokens kept) and releases its slot/pages at the next
        step/epoch boundary.  Raises ``AdmissionRejected`` when the
        request can never be served (empty prompt, no decode headroom,
        paged worst-case KV over the pool) or when the engine is
        shedding load (``max_queue_depth`` / ``max_queue_delay_s``)."""
        uid = self._uid
        self._uid += 1
        req = Request(uid=uid, tokens=np.asarray(tokens, np.int32),
                      max_new_tokens=max_new_tokens, stop_token=stop_token,
                      deadline_s=deadline_s)
        tr = self.tracer
        tr.track(request_tid(uid), f"req {uid}")
        tr.instant("submit", request_tid(uid), prompt_len=req.prompt_len,
                   max_new=max_new_tokens)
        if self.kv_mode == "paged":
            # must cover both the lifetime worst case AND the admission
            # gate's requirement (prompt + one step of headroom) — a
            # request _can_place can never pass would park the queue
            # forever once accepted
            worst = max(self._worst_case_entries(req),
                        (req.prompt_len + 1) * self.n_attn)
            if self.allocator.pages_for(worst) > self.num_pages:
                raise AdmissionRejected(
                    f"request {uid}: worst-case KV ({worst} entries) "
                    f"exceeds the page pool ({self.num_pages} pages × "
                    f"{self.page_size}) — OOM-safe admission impossible",
                    reason="kv_worst_case", uid=uid)
        self._maybe_shed(req)
        self.scheduler.submit(req)
        return RequestHandle(uid, self)

    def _maybe_shed(self, req: Request) -> None:
        """Load shedding at the submit boundary: refuse to grow a queue
        that is over the depth bound or whose *head* has already waited
        past the delay bound (the head's age is the deterministic lower
        bound on what a newcomer would wait — if the oldest queued
        request is past the bound, everything behind it is too)."""
        q = self.scheduler.queue
        reason = detail = None
        if (self.max_queue_depth is not None
                and len(q) >= self.max_queue_depth):
            reason = "queue_depth"
            detail = (f"queue depth {len(q)} at the shed bound "
                      f"{self.max_queue_depth}")
        elif self.max_queue_delay_s is not None and q:
            head_age = perf_counter() - q[0].submit_s
            if head_age > self.max_queue_delay_s:
                reason = "queue_delay"
                detail = (f"queue head has waited {head_age:.3f}s > "
                          f"bound {self.max_queue_delay_s:.3f}s")
        if reason is not None:
            self._shed_pending.append(reason)
            self.tracer.instant("shed", request_tid(req.uid), reason=reason)
            raise AdmissionRejected(
                f"request {req.uid} shed: {detail}", reason=reason,
                uid=req.uid)

    def cancel(self, uid: int) -> None:
        """Cooperative cancellation: mark ``uid`` for removal at the next
        step/epoch boundary — a queued request is dropped, an in-flight
        prefill is aborted, a resident finishes with its partial tokens
        (``finish_reason == "cancelled"``) and its slot/pages released.
        Unknown or already-finished uids are a no-op."""
        self._cancelled.add(uid)
        self.tracer.instant("cancel", request_tid(uid))

    # -- crash-consistent snapshots (serve/snapshot.py) --------------------
    def resume(self, snapshot_dir: Optional[str] = None,
               step: Optional[int] = None) -> int:
        """Load the newest (or the given ``step``) boundary snapshot under
        ``snapshot_dir`` (default: the engine's own) — the next ``run()``
        continues from it: scheduler queue/residents, allocator chains,
        finished results and the device KV state are all restored, so at
        temperature 0 the surviving requests' tokens are bit-identical to
        the run the dead process would have completed.  Returns the
        boundary index restored.  Requests submitted to this engine
        before ``run()`` are merged into the restored queue in age
        order."""
        snap_dir = snapshot_dir or self.snapshot_dir
        if snap_dir is None:
            raise ValueError("resume() needs a snapshot_dir (argument or "
                             "constructor)")
        template = {"kv": self._init_kv_state(), "rng": jax.random.PRNGKey(0)}
        device_tree, host, at = snapshot_mod.load_snapshot(
            snap_dir, template, step)
        snapshot_mod.check_fingerprint(self, host)
        self._resume = (device_tree, host, at)
        return at

    def _init_kv_state(self):
        """Fresh device KV state for the engine's mode (the run loops and
        the snapshot restore template build it the same way)."""
        if self.kv_mode == "paged":
            return paged_mod.init_store(self.cfg, self.num_pages,
                                        self.page_size,
                                        kv_dtype=self.kv_dtype)
        return init_pool(self.cfg, self.max_slots, self.max_len)

    def _acquire_store(self):
        """Device page store for one paged run.  The store outlives a
        single ``run()`` call: published prefix records alias page
        payloads, so the run loops stash their final store back on the
        engine at clean exit and the next run picks it up here.  Stale
        entries in re-allocated pages are harmless — the attention kernel
        masks by chain fill exactly as it does for within-run page reuse.

        Ownership is taken eagerly (the stash is cleared before the run
        starts): if the run dies mid-flight the store may have been
        donated away, so the next run starts from a fresh zeroed pool —
        and must flush the prefix cache, whose records would otherwise
        alias blank pages."""
        store = self._store
        self._store = None
        if store is not None:
            return store
        if self.prefix is not None:
            for slot in list(self._warm_pending):
                self._abort_warm(slot)
            self.prefix.clear()
        store = paged_mod.init_store(self.cfg, self.num_pages,
                                     self.page_size,
                                     kv_dtype=self.kv_dtype)
        if self.policy is not None:
            # head-sharded page pools, replicated entry metadata — the
            # host-side PageAllocator stays global (see cache_specs)
            store = jax.device_put(store, self._store_sh)
        return store

    # -- paged-mode memory policy -------------------------------------------
    def _worst_case_entries(self, req: Request) -> int:
        """Upper bound on one request's lifetime entry count: every stored
        token fresh at every attention layer (the last generated token is
        emitted but never fed, so it stores nothing)."""
        toks = min(self.max_len, req.prompt_len + req.max_new_tokens - 1)
        return toks * self.n_attn

    def _can_place(self, req: Request) -> bool:
        """Admission gate: enough *free pages* for the prompt's worst-case
        entries plus one decode step of headroom.  The run loop reserves
        every resident's next-step headroom *before* admission, so the
        free list seen here is what is genuinely spare — a newcomer is
        never admitted into pages the residents are about to need (which
        would just get it preempted back, throwing its prefill away).
        (Admission allocates only the measured entries afterwards, so this
        never over-commits.)"""
        need = req.prompt_len * self.n_attn + self.n_attn
        pages = self.allocator.pages_for(need)
        if pages > self.allocator.pages_per_slot:
            return False
        # prefix records hold pages too: evict LRU records (never pinned
        # ones) before declaring the pool full — cached history must not
        # starve admission
        while pages > self.allocator.free_pages and self._reclaim_pages():
            pass
        return pages <= self.allocator.free_pages

    def _reclaim_pages(self) -> bool:
        """Page-pressure valve: drop one LRU prefix record.  Returns True
        when a record was evicted (its unshared pages returned to the
        free list) — callers loop until the reservation fits or this
        returns False, *then* fall back to preempting residents."""
        return (self.prefix is not None
                and self.prefix.evict_one() is not None)

    # -- prefix sharing (docs/kvcache.md) ----------------------------------
    def _prefix_probe(self, req: Request, slot: int) -> int:
        """Scheduler admission hook (``kv.prefix_cache``): find the
        longest published prefix of ``req``'s prompt and alias its pages
        into ``slot`` — full shared pages by reference (refcount bump, no
        copy), the partial boundary page queued for a device-side COW
        copy at the first suffix chunk (the probe runs inside
        ``plan_step`` with no store handle in scope; deferring is safe
        because nothing reads the slot's pages before that chunk).  The
        cold *suffix*'s worst-case pages are reserved here too, keeping
        the reservation inside the same plan_step that passed
        ``_can_place`` — the invariant the cold path maintains.  Returns
        the number of prompt tokens covered (0 = cold admission)."""
        rec = self.prefix.lookup(req.tokens)
        if rec is None:
            if self.metrics is not None:
                self.metrics.inc("prefix_misses_total")
            return 0
        alloc, nA = self.allocator, self.n_attn
        n_full, rem = divmod(rec.entries, alloc.page_size)
        worst = rec.entries + (req.prompt_len - rec.length) * nA + nA
        alloc.alias_into(slot, rec.pages[:n_full])
        if not alloc.ensure(slot, worst):
            # cannot happen after _can_place's full-prompt worst-case
            # check (worst - aliased <= full worst case), but fall back to
            # a cold admission rather than crash on an allocator surprise
            alloc.release(slot)
            return 0
        alloc.seed_fill(slot, rec.entries)
        self.prefix.pin(rec)
        copy = None
        if rem:
            copy = (int(rec.pages[n_full]),
                    int(alloc.block_table[slot, n_full]), rem)
        self._warm_pending[slot] = _WarmAdmission(rec=rec, copy=copy)
        return rec.length

    def _abort_warm(self, slot: int) -> None:
        """Drop the warm-admission state of an aborted in-flight prefill.
        The caller's ``allocator.release`` already dropped the chain's
        page references (shared pages just lose one refcount); this
        unpins the record so it is evictable again."""
        if self.prefix is None:
            return
        warm = self._warm_pending.pop(slot, None)
        if warm is not None:
            self.prefix.unpin(warm.rec)

    # -- main loop ---------------------------------------------------------
    def run(self, rng: Optional[jax.Array] = None
            ) -> Dict[str, object]:
        """Drain the queue.  Returns {'results': {uid: RequestResult},
        'stats': ServeStats, 'metrics': MetricsRegistry} (stats is a
        derived view over the registry; the registry adds histograms,
        gauges and per-layer/per-step series — see docs/observability.md).
        Under a mesh the sharding policy is active
        for the whole run, so every jitted step traces with the serve-mode
        activation/KV hints baked in (routing gates and the Σy² carry stay
        replicated; KV is head-sharded).

        Reimplemented on the streaming driver: the run loops are
        generators yielding once per engine iteration (the granularity
        ``RequestHandle.tokens`` observes), and ``run()`` simply pumps
        the shared driver to exhaustion — token output and metrics are
        identical to the pre-streaming blocking loops.  A partially
        consumed ``tokens()`` iteration resumes here: one driver serves
        both surfaces."""
        if self._driver is None:
            self._driver_rng = rng
        while self._pump():
            pass
        out, self._driver_out = self._driver_out, None
        return out

    def _make_driver(self, rng):
        """One generator wrapping the mode dispatch; ``yield`` marks
        engine-iteration boundaries, ``return`` carries the run dict."""
        with set_policy(self.policy):
            if self.kv_mode == "paged":
                if self.spec_k:
                    return (yield from self._run_paged_spec(rng))
                if self.decode_steps > 1:
                    return (yield from self._run_paged_fused(rng))
                return (yield from self._run_paged(rng))
            if self.spec_k:
                return (yield from self._run_dense_spec(rng))
            if self.decode_steps > 1:
                return (yield from self._run_dense_fused(rng))
            return (yield from self._run_dense(rng))

    def _pump(self) -> bool:
        """Advance the shared driver one engine iteration.  Returns False
        when the run completed (the result dict lands in
        ``self._driver_out``).  Engine errors tear the driver down before
        re-raising, so a subsequent ``run()`` starts fresh."""
        if self._driver is None:
            self._driver = self._make_driver(self._driver_rng)
        try:
            next(self._driver)
            return True
        except StopIteration as e:
            self._driver = None
            self._driver_rng = None
            self._driver_out = e.value
            return False
        except BaseException:
            self._driver = None
            self._driver_rng = None
            raise

    # -- streaming emission (docs/serving.md) ------------------------------
    def _emit_stream(self, uid: int, out_tokens: List[int],
                     step: int) -> None:
        """Append tokens past the uid's high-water mark to its stream
        buffer.  The watermark survives preemption (out_tokens resets,
        the mark does not), so every emitted index streams exactly once —
        at temperature 0 a preempted request re-derives the identical
        prefix; at temperature > 0 re-decoded tokens may diverge from
        what was already streamed (documented caveat).  The request's
        ``first_token`` instant fires when the mark leaves 0, so once per
        request, preemptions included."""
        w = self._stream_pos.get(uid, 0)
        if len(out_tokens) > w:
            if not w:
                self.tracer.instant("first_token", request_tid(uid))
            buf = self._streams.setdefault(uid, [])
            buf.extend((int(t), step) for t in out_tokens[w:])
            self._stream_pos[uid] = len(out_tokens)

    def _drain_stream(self, rs: _RunState) -> None:
        """Per-iteration emission sweep over the resident slots.  Slots
        with an unresolved deferred first token (fused mode's
        ``rs.pending``) are skipped — their out_tokens[0] is still the
        placeholder; the post-epoch resolve backfills it and the next
        sweep emits."""
        for slot, st in self.scheduler.active.items():
            if slot not in rs.pending:
                self._emit_stream(st.req.uid, st.out_tokens, rs.step_idx)

    def _record_result(self, rs: _RunState, res: "RequestResult") -> None:
        """Single choke point for finished requests: the run dict and the
        streaming surface see the same RequestResult."""
        rs.results[res.uid] = res
        self._stream_results[res.uid] = res
        self._stream_done.add(res.uid)

    def _stream_tokens(self, uid: int):
        """Yield ``(token, step)`` for ``uid``, pumping the engine when
        the buffer runs dry.  Ends when the request has a final result
        (or the engine drains without it ever being placeable)."""
        buf = self._streams.setdefault(uid, [])
        sent = 0
        while True:
            while sent < len(buf):
                yield buf[sent]
                sent += 1
            if uid in self._stream_done:
                return
            if not self._pump() and sent >= len(buf) \
                    and uid not in self._stream_done:
                return

    # -- observability plumbing (shared by all four run loops) -------------
    def _new_run_state(self, rng: Optional[jax.Array],
                       paged: bool) -> _RunState:
        """Fresh per-run state: the stats shell, the metrics registry
        (this run's source of truth — ``_finalize`` derives ServeStats
        from it), request-lifecycle span openings for everything already
        queued, and the compile-probe baseline."""
        if paged:
            stats = ServeStats(kv_mode="paged", page_size=self.page_size,
                               pages_total=self.num_pages)
            hist = history_mod.HistoryAccounting(
                self.n_attn, self.max_slots,
                paged_mod.reuse_enabled(self.cfg))
        else:
            stats, hist = ServeStats(), None
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        rs = _RunState(stats=stats, results={}, t_run=perf_counter(),
                       rng=rng, hist=hist)
        rs.compiled_seen = jit_cache_size(self._jitted)
        self.metrics = rs.metrics
        self._export_linear_plans(rs.metrics)
        # credit submit-time load sheds to this run's registry (each one
        # already emitted its "shed" trace instant at submit)
        for _ in self._shed_pending:
            rs.metrics.inc("requests_shed_total")
        self._shed_pending.clear()
        tr = self.tracer
        for req in self.scheduler.queue:
            rs.traced.add(req.uid)
            tid = request_tid(req.uid)
            tr.track(tid, f"req {req.uid}")
            tr.begin("request", tid)
            tr.begin("queued", tid)
        return rs

    def _step_gauges(self, rs: _RunState) -> None:
        """Per-iteration scheduler/memory gauges + the trace counter row."""
        sched, m = self.scheduler, rs.metrics
        m.set("queue_depth", len(sched.queue))
        m.set("resident_slots", len(sched.active))
        vals = {"queue": len(sched.queue), "resident": len(sched.active)}
        if self.kv_mode == "paged":
            free = self.allocator.free_pages
            m.set("free_pages", free)
            m.set("pages_in_use", self.num_pages - free)
            vals["free_pages"] = free
        self.tracer.counter("sched", vals)

    def _note_admission(self, rs: _RunState) -> None:
        """Call right after ``plan_step``: if the FIFO head was just
        popped into a slot, close its queued span, open its prefill-phase
        span and observe its queue wait."""
        pf = self.scheduler.prefilling
        if pf is None or pf.req.uid in rs.admitted:
            return
        rs.admitted.add(pf.req.uid)
        if pf.req.submit_s:
            rs.metrics.observe("queue_wait_seconds",
                               perf_counter() - pf.req.submit_s)
        tid = request_tid(pf.req.uid)
        tr = self.tracer
        tr.end(tid)                       # queued
        tr.instant("admit", tid, slot=pf.slot)
        tr.begin("prefill", tid)

    def _count_deferrals(self, rs: _RunState) -> None:
        """Call once per iteration after its planning: the requests left
        queued while a slot stays free (``min(free slots, queued)``),
        counted by why — ``one_per_iteration`` when a prefill was
        admitted or is in flight, ``pages`` when ``can_place`` refused
        the queue's head — plus the ``admission`` trace counter row."""
        sched, m = self.scheduler, rs.metrics
        n = min(sched.free_slots, len(sched.queue))
        if n:
            m.inc("admissions_deferred_total", n,
                  reason=("one_per_iteration" if sched.prefilling
                          is not None else "pages"))
        tr = self.tracer
        if tr.enabled:
            tr.counter("admission", {
                r: m.value("admissions_deferred_total", reason=r)
                for r in ("one_per_iteration", "pages")})

    def _walk_state(self, j: int) -> Dict[str, int]:
        """The residents before a paged decode dispatch: how many, their
        positions and their stored entries summed, and ``J``, the
        block-table width in pages the attention walks for every slot
        (the ``dispatch`` span's arguments)."""
        active, fill = self.scheduler.active, self.allocator.fill
        return {"residents": len(active),
                "positions": sum(st.pos for st in active.values()),
                "entries": sum(int(fill[s]) for s in active), "J": j}

    def _count_walk(self, rs: _RunState, steps: int, j: int, live: int,
                    valid: int) -> None:
        """Entries the paged decode walks, after ``steps`` dispatched
        steps: each step reads every slot's block table up to ``j``
        pages at each attention layer (``walked``); ``live`` and
        ``valid`` are the fills and positions of the slots active in
        each step, summed over the steps (the chain holds ``fill``
        entries, of which each layer needs one per position).  Plus the
        ``kv_walk`` trace counter row of the cumulative values."""
        m, nA = rs.metrics, self.n_attn
        name = "paged_walk_entries_total"
        m.inc(name, nA * self.max_slots * j * self.page_size * steps,
              part="walked")
        m.inc(name, nA * live, part="live")
        m.inc(name, nA * valid, part="valid")
        tr = self.tracer
        if tr.enabled:
            tr.counter("kv_walk", {p: m.value(name, part=p)
                                   for p in ("walked", "live", "valid")})

    def _poll_compiles(self, rs: _RunState) -> None:
        """Surface jit-cache growth (new prefill buckets, pow2 epoch
        lengths, block-table widths) as a counter + trace instants, so
        recompiles are attributable to the iteration that caused them."""
        n = jit_cache_size(self._jitted)
        if n > rs.compiled_seen:
            rs.metrics.inc("compiles_total", n - rs.compiled_seen)
            self.tracer.instant("compile", n_new=n - rs.compiled_seen)
            rs.compiled_seen = n
            self._export_linear_plans(rs.metrics)

    def _export_linear_plans(self, m: MetricsRegistry) -> None:
        """The fused-linear tiles traced so far, into the registry (on a
        new run and after a compile, never per step): grid steps per call
        shape and step (``fused_linear_grid_steps{call,phase}``) and how
        many call shapes found no lane-aligned tile
        (``fused_linear_plan_fallbacks_total``)."""
        plans = self._linear_plans
        for (call, phase), plan in plans.items():
            m.set("fused_linear_grid_steps", plan.steps, call=call,
                  phase=phase)
        m.set("fused_linear_plan_fallbacks_total",
              sum(p.fallback for p in plans.values()))

    def _record_step_series(self, rs: _RunState, lay_keep) -> None:
        """Per-step telemetry time series: per-layer attention-gate keep
        rate (``attn_keep_rate{layer=i}``) and the running measured
        KV-saved fraction, both indexed by cumulative decode step."""
        m = rs.metrics
        if lay_keep is not None:
            for i, v in enumerate(lay_keep):
                m.record("attn_keep_rate", rs.step_idx, float(v), layer=i)
        dense = m.value("kv_entries_dense_measured_total")
        if dense:
            m.record("kv_saved_fraction", rs.step_idx,
                     1.0 - m.value("kv_entries_stored_measured_total")
                     / dense)

    # -- robustness: boundary pass, fault seams, watchdog ------------------
    def _boundary(self, rs: _RunState, kv_state) -> None:
        """Step/epoch-boundary pass shared by all four run loops, run
        before each iteration's dispatch: (1) the request-lifecycle sweep
        (cooperative cancellation + deadline expiry — resources release
        within one step/epoch of the event); (2) a crash-consistent
        snapshot when due; (3) the injected host kill, which fires
        *after* the boundary snapshot so a resume loses nothing."""
        self._lifecycle(rs)
        self._maybe_snapshot(rs, kv_state)
        f = self.faults.take("kill", rs.disp_idx)
        if f is not None:
            rs.metrics.inc("faults_injected_total")
            self.tracer.instant("fault", kind="kill", step=rs.disp_idx)
            raise SimulatedKill(
                f"injected host kill at boundary {rs.disp_idx}: {f.message}",
                trace_path=self._flush_trace())

    def _expired(self, req: Request, now: float) -> Optional[str]:
        """The request's lifecycle verdict at ``now``: "cancelled",
        "deadline", or None (keep going)."""
        if req.uid in self._cancelled:
            return "cancelled"
        if (req.deadline_s is not None and req.submit_s
                and now - req.submit_s > req.deadline_s):
            return "deadline"
        return None

    def _lifecycle(self, rs: _RunState) -> None:
        """Sweep every request the engine holds — queued, mid-prefill,
        resident — for cancellation / deadline expiry and retire the hits
        (slot + pages released, typed finish reason, trace span closed)."""
        sched = self.scheduler
        now = perf_counter()
        for req in list(sched.queue):
            reason = self._expired(req, now)
            if reason is not None:
                sched.remove_queued(req.uid)
                self.tracer.end(request_tid(req.uid))    # queued span
                self._finish_unplaced(rs, req, reason)
        pf = sched.prefilling
        if pf is not None:
            reason = self._expired(pf.req, now)
            if reason is not None:
                sched.abort_prefill(requeue=False)
                if self.kv_mode == "paged":
                    self.allocator.release(pf.slot)
                    self._abort_warm(pf.slot)
                rs.stage_cache = None
                rs.stage_gates = []
                rs.admitted.discard(pf.req.uid)
                self.tracer.end(request_tid(pf.req.uid))  # prefill span
                self._finish_unplaced(rs, pf.req, reason)
        for slot in sorted(sched.active):
            st = sched.active[slot]
            reason = self._expired(st.req, now)
            if reason is not None:
                tok_dev = rs.pending.pop(slot, None)
                if tok_dev is not None:
                    # materialize the deferred first token so the partial
                    # result carries the real value, not the placeholder
                    tok = int(np.asarray(tok_dev)[0])
                    st.out_tokens[0] = tok
                    st.next_token = tok
                self._finish(rs, slot, reason)

    def _finish_unplaced(self, rs: _RunState, req: Request,
                         reason: str) -> None:
        """Retire a request that never (or no longer) holds a slot —
        removed from the queue or aborted mid-prefill — with an empty
        token result and a typed reason."""
        self._cancelled.discard(req.uid)
        self._record_result(rs, RequestResult(
            uid=req.uid, tokens=np.zeros((0,), np.int32),
            prompt_len=req.prompt_len, ttft_s=0.0, decode_s=0.0,
            finish_reason=reason))
        self._count_lifecycle(rs, reason)
        tid = request_tid(req.uid)
        self.tracer.instant("finish", tid, reason=reason, tokens=0)
        if req.uid in rs.traced:
            self.tracer.end(tid)          # close the request root span

    def _count_lifecycle(self, rs: _RunState, reason: str) -> None:
        if reason == "cancelled":
            rs.metrics.inc("requests_cancelled_total")
        elif reason == "deadline":
            rs.metrics.inc("deadline_exceeded_total")
        elif reason == "preempt_budget":
            rs.metrics.inc("preempt_budget_exhausted_total")

    def _maybe_snapshot(self, rs: _RunState, kv_state) -> None:
        """Publish a crash-consistent snapshot when one is due and the
        boundary is quiescent (no prefill in flight, no deferred first
        tokens, no staging cache) — at such a boundary host structures +
        device KV are the complete engine state (serve/snapshot.py)."""
        if self.snapshot_dir is None or kv_state is None:
            return
        if rs.disp_idx - max(rs.last_snap, 0) < self.snapshot_every \
                or rs.disp_idx == 0:
            return
        if (self.scheduler.prefilling is not None or rs.pending
                or rs.stage_cache is not None):
            return
        with self.tracer.span("snapshot", step=rs.disp_idx):
            host = snapshot_mod.encode_host_state(self, rs)
            snapshot_mod.save_snapshot(
                self.snapshot_dir, rs.disp_idx,
                {"kv": kv_state, "rng": rs.rng}, host)
        rs.last_snap = rs.disp_idx
        rs.metrics.inc("snapshots_total")
        self.tracer.instant("snapshot", step=rs.disp_idx)

    def _apply_resume(self, rs: _RunState, kv_state):
        """Consume a pending ``resume()``: rebuild the host state, swap
        in the restored device KV (re-placed under the engine's
        shardings when meshed), and reopen trace spans for the restored
        requests.  Returns the KV state the run loop should use."""
        if self._resume is None:
            return kv_state
        device_tree, host, at = self._resume
        self._resume = None
        snapshot_mod.apply_host_state(self, rs, host)
        rs.last_snap = at
        rs.rng = device_tree["rng"]
        kv = device_tree["kv"]
        if self.policy is not None:
            sh = (self._store_sh if self.kv_mode == "paged"
                  else self._pool_sh)
            kv = jax.device_put(kv, sh)
        tr = self.tracer
        for req in self.scheduler.queue:
            if req.uid not in rs.traced:
                rs.traced.add(req.uid)
                tid = request_tid(req.uid)
                tr.track(tid, f"req {req.uid}")
                tr.begin("request", tid)
                tr.begin("queued", tid)
        for st in self.scheduler.active.values():
            uid = st.req.uid
            rs.traced.add(uid)
            rs.admitted.add(uid)
            tid = request_tid(uid)
            tr.track(tid, f"req {uid}")
            tr.begin("request", tid)
        rs.metrics.inc("resumes_total")
        tr.instant("resume", step=at)
        return kv

    def _fault_dispatch(self, rs: _RunState) -> None:
        """Dispatch-seam fault: raise the scheduled ``FaultInjected``
        *before* the jitted call (donated buffers untouched) — the run
        loop's retry path abandons the iteration and re-plans."""
        f = self.faults.take("dispatch_error", rs.disp_idx)
        if f is not None:
            rs.metrics.inc("faults_injected_total")
            self.tracer.instant("fault", kind="dispatch_error",
                                step=rs.disp_idx)
            raise FaultInjected(f.message)

    def _fault_stall(self, rs: _RunState) -> None:
        """Sync-seam fault: sleep inside the sync span, emulating a hung
        device dispatch the watchdog then observes."""
        f = self.faults.take("stall", rs.disp_idx)
        if f is not None:
            rs.metrics.inc("faults_injected_total")
            self.tracer.instant("fault", kind="stall", step=rs.disp_idx,
                                stall_s=f.stall_s)
            sleep_stall(f.stall_s)

    def _fault_oom(self, rs: _RunState) -> List[int]:
        """Headroom-seam fault (paged): hide free pages for this
        iteration so reservations fail exactly as if residents had
        filled the pool; the run loop returns them via
        ``allocator.unhide_pages`` before admission."""
        f = self.faults.take("oom", rs.disp_idx)
        if f is None:
            return []
        hidden = self.allocator.hide_pages(f.pages)
        rs.metrics.inc("faults_injected_total")
        self.tracer.instant("fault", kind="oom", step=rs.disp_idx,
                            pages=len(hidden))
        return hidden

    def _watch(self, rs: _RunState, phase: str, seconds: float) -> None:
        """Feed one dispatch+sync wall time to the watchdog; a straggler
        strike is counted and traced, a hard-timeout breach flushes the
        trace and re-raises ``HungDispatch`` with its path attached."""
        wd = self.watchdog
        if wd is None:
            return
        try:
            if wd.observe(phase, seconds):
                rs.metrics.inc("watchdog_strikes_total")
                self.tracer.instant("watchdog", phase=phase,
                                    elapsed_s=round(seconds, 6),
                                    strikes=wd.strikes)
        except HungDispatch as e:
            rs.metrics.inc("watchdog_timeouts_total")
            self.tracer.instant("watchdog", phase=phase,
                                elapsed_s=round(seconds, 6), timeout=True)
            e.trace_path = self._flush_trace()
            raise

    def _flush_trace(self) -> Optional[str]:
        """Best-effort trace flush on the abort path (open spans and all)
        so the failure is diagnosable post-mortem; returns the path."""
        tr = self.tracer
        if tr.enabled and tr.path is not None:
            tr.save()
            return str(tr.path)
        return None

    # -- run-loop bookkeeping shared by both KV modes ----------------------
    @staticmethod
    def _make_result(st: ActiveRequest, reason: str) -> RequestResult:
        st.finish_reason = reason
        return RequestResult(
            uid=st.req.uid,
            tokens=np.asarray(st.out_tokens, np.int32),
            prompt_len=st.req.prompt_len,
            ttft_s=st.first_token_s - st.submit_s,
            decode_s=st.decode_s,
            finish_reason=reason,
            kv_stored=st.kv_stored,
            kv_dense=st.kv_dense,
            max_decode_stall_s=st.max_stall_s,
        )

    def _account_prefill(self, rs: _RunState, st: ActiveRequest) -> None:
        """Fold the prompt-phase gate log into the request's measured
        KV-storage accounting (layer-0 dense + executed layers — the same
        counting ``paged.prefill_entry_count`` uses for the entry stream).
        Resolved at finish time: the gate log may still be a device array
        from the prefill dispatch, and by now it is long since computed,
        so the conversion is a copy, not a pipeline stall."""
        if st.pf_gates is None:
            return
        T0 = st.req.prompt_len
        L = max(len(self.cfg.attention_layers), 1)
        measure = self.cfg.skip.enabled and self.cfg.skip.kv_reuse
        if measure:
            g = np.asarray(st.pf_gates, np.float32)[:, :T0]
            stored = T0 + int((g[1:] > 0.5).sum())
        else:
            stored = L * T0
        st.kv_dense += L * T0
        st.kv_stored += stored
        rs.metrics.inc("kv_entries_dense_measured_total", L * T0)
        rs.metrics.inc("kv_entries_stored_measured_total", stored)
        st.pf_gates = None

    def _finish(self, rs: _RunState, slot: int, reason: str) -> None:
        """Evict ``slot``'s request and record its result (paged mode also
        returns its pages and clears its history accounting)."""
        st = self.scheduler.release(slot)
        self._account_prefill(rs, st)
        if self.kv_mode == "paged":
            self.allocator.release(slot)
            rs.hist.on_release(slot)
        self._emit_stream(st.req.uid, st.out_tokens, rs.step_idx)
        res = self._make_result(st, reason)
        self._record_result(rs, res)
        self._cancelled.discard(st.req.uid)
        self._count_lifecycle(rs, reason)
        m = rs.metrics
        m.inc("requests_completed_total")
        m.observe("ttft_seconds", res.ttft_s)
        n = res.decode_tokens - 1
        if n > 0 and res.decode_s > 0:
            m.observe("tpot_seconds", res.decode_s / n)
        tid = request_tid(st.req.uid)
        self.tracer.instant("finish", tid, reason=reason,
                            tokens=res.decode_tokens)
        self.tracer.end(tid)              # close the request root span

    def _preempt_youngest(self, rs: _RunState, exclude: int) -> bool:
        """OOM backpressure (paged mode): evict the *youngest* request —
        by original ``submit_s``, which requeueing preserves — (≠
        ``exclude``) and put it back into the queue at its age-ordered
        position; its pages return to the free list and it will
        re-prefill from scratch when memory frees up.  An in-flight
        chunked prefill is always the newest admission and holds its
        worst-case reservation without yet being a resident, so it is
        aborted first (no decode progress lost; decode steps between the
        abort and the re-try keep the residents progressing, so this
        cannot livelock).

        Victim age is the request's original submission stamp, NOT its
        admission recency: under the old admission-order rule a
        re-admitted request became "newest" again and the same request
        could be re-victimized forever while genuinely younger residents
        ran to completion (the preemption-storm starvation the
        ``test_fault_tolerance.py`` fairness regression pins down).  A
        victim past the ``max_preemptions`` retry budget finishes with
        its partial tokens (reason "preempt_budget") instead of
        requeueing."""
        sched = self.scheduler
        m, tr = rs.metrics, self.tracer
        pf = sched.prefilling
        if pf is not None and pf.slot != exclude:
            sched.abort_prefill(requeue=False)
            self.allocator.release(pf.slot)
            self._abort_warm(pf.slot)
            rs.stage_cache = None
            rs.stage_gates = []
            m.inc("preemptions_total")
            rs.admitted.discard(pf.req.uid)
            pf.req.preempt_count += 1
            tid = request_tid(pf.req.uid)
            tr.end(tid)                   # abort the open prefill span
            tr.instant("preempt", tid, kind="prefill_abort",
                       count=pf.req.preempt_count)
            if self._budget_spent(pf.req):
                self._finish_unplaced(rs, pf.req, "preempt_budget")
            else:
                sched.requeue(pf.req)     # age-preserving re-admission
                tr.begin("queued", tid)
            return True
        victims = [s for s in sched.active if s != exclude]
        if not victims:
            return False
        slot = max(victims, key=lambda s: sched.active[s].req.submit_s)
        st = sched.release(slot)
        self.allocator.release(slot)
        rs.hist.on_release(slot)
        rs.pending.pop(slot, None)
        m.inc("preemptions_total")
        rs.admitted.discard(st.req.uid)
        st.req.preempt_count += 1
        tid = request_tid(st.req.uid)
        tr.instant("preempt", tid, kind="evict", slot=slot,
                   count=st.req.preempt_count)
        if self._budget_spent(st.req):
            self._account_prefill(rs, st)
            self._emit_stream(st.req.uid, st.out_tokens, rs.step_idx)
            self._record_result(rs, self._make_result(st, "preempt_budget"))
            self._cancelled.discard(st.req.uid)
            self._count_lifecycle(rs, "preempt_budget")
            tr.instant("finish", tid, reason="preempt_budget",
                       tokens=len(st.out_tokens))
            tr.end(tid)                   # close the request root span
        else:
            sched.requeue(st.req)         # age-preserving re-admission
            tr.begin("queued", tid)
        return True

    def _budget_spent(self, req: Request) -> bool:
        return (self.max_preemptions is not None
                and req.preempt_count > self.max_preemptions)

    def _activate_prefilled(self, rs: _RunState, req: Request, slot: int,
                            tok: int, now: float, tok_known: bool = True):
        """Register a freshly prefilled request.  Returns (state, reason):
        reason is "stop"/"length" when the first token already ends the
        request, else None.  ``tok_known=False`` (fused mode): ``tok`` is
        a placeholder — the real value is still a device array, the stop
        check happens on device at the next epoch's loop entry, and the
        host backfills the bookkeeping at the epoch sync."""
        rs.metrics.inc("prefill_tokens_total", req.prompt_len)
        rs.metrics.inc("decode_tokens_total")
        st = ActiveRequest(req=req, slot=slot, pos=req.prompt_len,
                           next_token=tok, out_tokens=[tok],
                           submit_s=req.submit_s, first_token_s=now,
                           last_emit_s=now)
        self.scheduler.activate(st)
        if tok_known and req.stop_token is not None \
                and tok == req.stop_token:
            return st, "stop"
        if req.max_new_tokens <= 1:
            return st, "length"
        return st, None

    def _advance_slot(self, rs: _RunState, st: ActiveRequest, tok: int,
                      g: Optional[np.ndarray], step_s: float,
                      measure: bool, n_layers: int) -> Optional[str]:
        """Post-decode bookkeeping for one resident (the fed token's KV
        was just written at st.pos).  Returns the finish reason or None."""
        m = rs.metrics
        st.decode_s += step_s
        now = perf_counter()
        if st.last_emit_s:
            gap = now - st.last_emit_s
            st.max_stall_s = max(st.max_stall_s, gap)
            m.observe("decode_stall_seconds", gap)
        st.last_emit_s = now
        if g is not None:
            stored = (1 + int(g[1:].sum()) if measure else n_layers)
            st.kv_dense += n_layers
            st.kv_stored += stored
            m.inc("kv_entries_dense_measured_total", n_layers)
            m.inc("kv_entries_stored_measured_total", stored)
        st.pos += 1
        st.out_tokens.append(tok)
        st.next_token = tok
        m.inc("decode_tokens_total")
        if st.req.stop_token is not None and tok == st.req.stop_token:
            return "stop"
        if len(st.out_tokens) >= st.req.max_new_tokens:
            return "length"
        if st.pos >= self.max_len:
            return "max_len"
        return None

    # -- prefill work units (monolithic or one chunk) ----------------------
    def _chunk_forward(self, rs: _RunState, work: PrefillChunk,
                       width: Optional[int] = None):
        """Run one staged prefill chunk.  Returns the chunk logits (valid
        only on the last chunk).  The gate log is accumulated as device
        arrays — paged packing consumes it at completion, and the dense
        path folds it into the measured KV-storage accounting at finish
        time; either way, never a per-chunk host sync.

        ``width`` overrides the dispatch width (warm-prefix suffix chunks
        in monolithic mode, where ``prefill_chunk == 0`` and the suffix
        runs through ``_warm_chunk_step`` at a pow2-padded width).  A warm
        admission pre-seeds ``rs.stage_cache`` from the shared pages, so
        the first-chunk init is guarded on it being absent."""
        C = self.prefill_chunk if width is None else width
        step = self._chunk_step if width is None else self._warm_chunk_step
        if work.is_first and rs.stage_cache is None:
            rs.stage_cache = model_lib.init_chunk_cache(
                self.cfg, 1, self._chunk_cap)
            if self.policy is not None:
                # place the fresh staging rows under their head-sharded
                # NamedShardings up front (donation then stays in place)
                rs.stage_cache = jax.device_put(rs.stage_cache,
                                                self._chunk_sh)
            rs.stage_gates = []
        c = len(work.tokens)
        padded = np.pad(work.tokens, (0, C - c))
        logits, rs.stage_cache, cstats = step(
            self.params, rs.stage_cache,
            {"tokens": jnp.asarray(padded[None])},
            jnp.int32(work.start),
            jnp.asarray([c - 1], jnp.int32))
        if "attn_gate" in cstats:
            rs.stage_gates.append(cstats["attn_gate"])
        return logits

    def _finish_prefill(self, rs: _RunState, work: PrefillChunk, tok_dev,
                        t0: float, pf_gates=None) -> None:
        """Activate a request whose prefill — first-token sampling folded
        into the prefill dispatch itself — just completed.  Single-step
        mode syncs the token here (this is the only host sync on the
        completion path; the per-token eager ``sample`` is gone).  Fused
        dense mode (``decode_steps > 1``) defers even that: the token
        stays a device array in ``rs.pending``, the next epoch's decode
        loop overlays it into the feed carry (with the stop check running
        on device at loop entry), and ``_resolve_pending`` backfills the
        host bookkeeping at the epoch sync.  ``pf_gates`` is the prompt's
        execution-gate log ([L, Tp], device or host), folded into the
        measured KV accounting at finish time by ``_account_prefill``."""
        defer = (self.decode_steps > 1 and self.kv_mode == "dense"
                 and work.req.max_new_tokens > 1)
        m = rs.metrics
        if defer:
            tok = 0                       # placeholder; device holds truth
        else:
            ts = perf_counter()
            tok = int(np.asarray(tok_dev)[0])
            m.inc("device_seconds_total", perf_counter() - ts)
        now = perf_counter()
        m.inc("prefill_chunks_total")
        m.inc("prefill_seconds_total", now - t0)
        self.scheduler.prefill_advance(work)
        st, reason = self._activate_prefilled(rs, work.req, work.slot, tok,
                                              now, tok_known=not defer)
        st.pf_gates = pf_gates
        self.tracer.end(request_tid(work.req.uid))    # prefill phase span
        if defer:
            rs.pending[work.slot] = tok_dev
        elif reason:
            self._finish(rs, work.slot, reason)

    def _resolve_pending(self, rs: _RunState) -> None:
        """Backfill host bookkeeping for first tokens deferred as device
        arrays by fused-mode ``_finish_prefill``.  Called at an epoch
        sync — the values are long since computed, so the conversion is
        a copy, not a stall.  A deferred first token that IS the stop
        token was entry-killed on device (the slot sat out the epoch, KV
        frozen), so finishing it here exactly mirrors the single-step
        engine's completion-time stop check."""
        for slot in list(rs.pending):
            tok_dev = rs.pending.pop(slot)
            st = self.scheduler.active.get(slot)
            if st is None or st.slot != slot:
                continue                  # stale (slot preempted/reused)
            tok = int(np.asarray(tok_dev)[0])
            st.out_tokens[0] = tok
            st.next_token = tok
            if (st.req.stop_token is not None and tok == st.req.stop_token
                    and len(st.out_tokens) == 1):
                self._finish(rs, slot, "stop")

    def _prefill_work_dense(self, rs: _RunState, work: PrefillChunk, pool):
        """Execute one dense-pool prefill work unit: either a legacy
        monolithic (bucketed) prefill + pool insert, or one staging-cache
        chunk (inserted into the pool on the last chunk)."""
        t0 = perf_counter()
        tr = self.tracer
        tid = request_tid(work.req.uid)
        if not self.prefill_chunk:
            with tr.span("prefill[0]", tid, tokens=work.req.prompt_len):
                padded, last = self.scheduler.pad_prompt(work.req.tokens)
                rs.rng, sub = jax.random.split(rs.rng)
                tok_dev, cache, pstats = self._prefill(
                    self.params, {"tokens": jnp.asarray(padded[None])},
                    jnp.asarray([last], jnp.int32), sub)
                pool = self._insert(pool, cache, jnp.int32(work.slot))
            pf_gates = pstats.get("attn_gate")
            if pf_gates is not None:
                pf_gates = pf_gates[:, 0]                         # [L, Tp]
        else:
            idx = work.start // self.prefill_chunk
            with tr.span(f"prefill[{idx}]", tid, tokens=len(work.tokens)):
                logits = self._chunk_forward(rs, work)
            if not work.is_last:
                # no sync: the chunk's compute overlaps the decode step
                # dispatched right after it (async dispatch stream), so
                # prefill time here attributes host-side dispatch only
                rs.metrics.inc("prefill_chunks_total")
                rs.metrics.inc("prefill_seconds_total", perf_counter() - t0)
                self.scheduler.prefill_advance(work)
                return pool
            pool = self._insert_staged(pool, rs.stage_cache,
                                       jnp.int32(work.slot))
            rs.stage_cache = None
            rs.rng, sub = jax.random.split(rs.rng)
            tok_dev = self._sample_tok(logits, sub)
            pf_gates = (jnp.concatenate(rs.stage_gates, axis=2)[:, 0]
                        if rs.stage_gates else None)
            rs.stage_gates = []
        self._finish_prefill(rs, work, tok_dev, t0, pf_gates)
        return pool

    def _prefill_work_paged(self, rs: _RunState, work: PrefillChunk, store):
        """Execute one paged prefill work unit: prefill (monolithic or one
        chunk), then pack the measured compact entries page-granular
        through the ``PageAllocator`` once the prompt completes.  Chunked
        mode reserves the prompt's worst-case pages at the first chunk —
        chunk steps span engine iterations whose resident decode appends
        also draw from the free list, so the completion-time pack must
        never find the admission-time pages gone."""
        cfg, alloc, nA = self.cfg, self.allocator, self.n_attn
        reuse = paged_mod.reuse_enabled(cfg)
        req, slot = work.req, work.slot
        if self.prefix is not None and slot in self._warm_pending:
            return self._prefill_work_warm(rs, work, store)
        t0 = perf_counter()
        tr = self.tracer
        tid = request_tid(req.uid)
        if not self.prefill_chunk:
            T0 = req.prompt_len
            with tr.span("prefill[0]", tid, tokens=T0):
                padded, last = self.scheduler.pad_prompt(req.tokens)
                rs.rng, sub = jax.random.split(rs.rng)
                tok_dev, cache, pstats = self._prefill_paged(
                    self.params, {"tokens": jnp.asarray(padded[None])},
                    jnp.asarray([last], jnp.int32), sub)
            gates = np.asarray(pstats["attn_gate"], np.float32)[:, 0]
        else:
            # worst-case pages were reserved at admission time in
            # _run_paged (the reservation must not trail the _can_place
            # check across iterations)
            idx = work.start // self.prefill_chunk
            with tr.span(f"prefill[{idx}]", tid, tokens=len(work.tokens)):
                logits = self._chunk_forward(rs, work)
            if not work.is_last:
                # no sync: chunk compute overlaps this iteration's decode
                # step (see _prefill_work_dense)
                rs.metrics.inc("prefill_chunks_total")
                rs.metrics.inc("prefill_seconds_total", perf_counter() - t0)
                self.scheduler.prefill_advance(work)
                return store
            T0 = req.prompt_len
            cache = rs.stage_cache
            gates = np.concatenate(
                [np.asarray(g, np.float32) for g in rs.stage_gates],
                axis=2)[:, 0]                                     # [nA, Tp]
            rs.stage_cache = None
            rs.stage_gates = []
            rs.rng, sub = jax.random.split(rs.rng)
            tok_dev = self._sample_tok(logits, sub)
        n_ent = paged_mod.prefill_entry_count(gates, T0, reuse)
        if not alloc.ensure(slot, n_ent + nA):
            raise PageExhausted(
                "page reservation failed after a successful _can_place "
                "worst-case check — allocator bug", slot=slot,
                free_pages=alloc.free_pages, pages_total=self.num_pages)
        store = self._pack(store, cache, jnp.asarray(gates), jnp.int32(T0),
                           jnp.asarray(alloc.block_table[slot]),
                           jnp.int32(0), jnp.int32(0))
        alloc.append(slot, n_ent, nA * T0)
        rs.hist.on_prefill(slot, gates, T0)
        if self.prefix is not None:
            self.prefix.publish(req.tokens, gates, alloc.chain(slot))
        self._finish_prefill(rs, work, tok_dev, t0, gates)
        return store

    def _prefill_work_warm(self, rs: _RunState, work: PrefillChunk, store):
        """Warm-prefix prefill work unit: the scheduler already cut the
        prompt down to the cold suffix (``work.start`` == the record's
        token length), so this path never runs forward over the shared
        prefix.  On the first suffix chunk it materialises the state the
        admission probe deferred — the COW copy of the partial boundary
        page, then a batch-1 staging cache reconstructed from the shared
        entry stream (``views_from_pages``; dequantised exactly, since
        page scales are powers of two) — and from there the ordinary
        chunk-resumable prefill machinery takes over.  Completion packs
        *only the suffix entries* (``start_token``/``start_entry`` offsets
        into ``pack_prefill``), stitches the record's gate log to the
        suffix gates so history/accounting/publish see the full-prompt
        view, and republishes the now-longer chain."""
        cfg, alloc, nA = self.cfg, self.allocator, self.n_attn
        reuse = paged_mod.reuse_enabled(cfg)
        req, slot = work.req, work.slot
        warm = self._warm_pending[slot]
        rec = warm.rec
        Ts, E_s = rec.length, rec.entries
        t0 = perf_counter()
        tr = self.tracer
        tid = request_tid(req.uid)
        m = rs.metrics
        if work.is_first:
            if warm.copy is not None:
                src, dst, keep = warm.copy
                with tr.span("cow_copy", tid, entries=keep):
                    store = self._cow_copy(store, jnp.int32(src),
                                           jnp.int32(dst), jnp.int32(keep))
            with tr.span("warm_restore", tid, tokens=Ts, entries=E_s):
                rs.stage_cache = self._warm_cache(
                    store, jnp.asarray(alloc.block_table[slot]),
                    jnp.int32(E_s))
            rs.stage_gates = []
            m.inc("prefix_hits_total")
            m.inc("prefix_tokens_saved_total", Ts)
            tr.instant("prefix_hit", tid, warm_tokens=Ts, entries=E_s)
        c = len(work.tokens)
        if self.prefill_chunk:
            width = None
            idx = (work.start - Ts) // self.prefill_chunk
        else:
            # monolithic mode: one pow2-padded suffix dispatch through the
            # max_len-capacity warm chunk step (clamped so the padded
            # write never runs past the staging cache)
            width = 1 << max(3, (c - 1).bit_length())
            if Ts + width > self._warm_cap:
                width = c
            idx = 0
        with tr.span(f"prefill[{idx}]", tid, tokens=c, warm=Ts):
            logits = self._chunk_forward(rs, work, width=width)
        if not work.is_last:
            m.inc("prefill_chunks_total")
            m.inc("prefill_seconds_total", perf_counter() - t0)
            self.scheduler.prefill_advance(work)
            return store
        T0 = req.prompt_len
        cache = rs.stage_cache
        suffix_gates = np.concatenate(
            [np.asarray(g, np.float32) for g in rs.stage_gates],
            axis=2)[:, 0]                              # [nA, >= T0 - Ts]
        gates = np.concatenate(
            [np.asarray(rec.gates, np.float32), suffix_gates], axis=1)
        rs.stage_cache = None
        rs.stage_gates = []
        rs.rng, sub = jax.random.split(rs.rng)
        tok_dev = self._sample_tok(logits, sub)
        n_suffix = int(history_mod.host_fresh_mask(
            suffix_gates, reuse)[:, :T0 - Ts].sum())
        if not alloc.ensure(slot, E_s + n_suffix + nA):
            raise PageExhausted(
                "warm-suffix page reservation failed after the probe's "
                "worst-case reservation — allocator bug", slot=slot,
                free_pages=alloc.free_pages, pages_total=self.num_pages)
        store = self._pack(store, cache, jnp.asarray(gates), jnp.int32(T0),
                           jnp.asarray(alloc.block_table[slot]),
                           jnp.int32(Ts), jnp.int32(E_s))
        alloc.append(slot, n_suffix, nA * (T0 - Ts))
        rs.hist.on_prefill(slot, gates, T0)
        self.prefix.publish(req.tokens, gates, alloc.chain(slot))
        self.prefix.unpin(rec)
        del self._warm_pending[slot]
        self._finish_prefill(rs, work, tok_dev, t0, gates)
        return store

    def _run_dense(self, rng: Optional[jax.Array] = None
                   ) -> Dict[str, object]:
        """Fixed ``max_slots × max_len`` pool (the original engine mode).

        Per iteration: consume ``plan_step`` plans — with chunking off the
        prefill plans are drained first (the legacy admission order:
        every placeable queued request prefills monolithically before the
        decode step); with ``prefill_chunk > 0`` exactly one chunk runs
        per iteration, so resident decodes proceed *between* chunks —
        then one ragged decode step over every resident slot."""
        cfg = self.cfg
        sched = self.scheduler
        rs = self._new_run_state(rng, paged=False)
        m, tr = rs.metrics, self.tracer
        L_attn = max(len(cfg.attention_layers), 1)
        measure = cfg.skip.enabled and cfg.skip.kv_reuse

        pool = init_pool(cfg, self.max_slots, self.max_len)
        if self.policy is not None:
            # commit every pool row to its NamedSharding before the first
            # donated step — host-side insert/evict then always sees (and
            # scatters into) head-sharded rows
            pool = jax.device_put(pool, self._pool_sh)
        pool = self._apply_resume(rs, pool)
        feed = np.zeros((self.max_slots,), np.int32)
        pos = np.zeros((self.max_slots,), np.int32)
        t_loop = perf_counter()

        while sched.has_work():
            self._boundary(rs, pool)
            if not sched.has_work():      # lifecycle sweep drained the run
                break
            tr.begin("step", idx=rs.disp_idx)
            self._step_gauges(rs)
            # -- prefill work from the step planner ------------------------
            pre_active = bool(sched.active)
            did_prefill = False
            while True:
                with tr.span("plan"):
                    plan = sched.plan_step(token_budget=self.step_tokens)
                self._note_admission(rs)
                if plan.prefill is None:
                    break
                with tr.span("prefill"):
                    pool = self._prefill_work_dense(rs, plan.prefill, pool)
                did_prefill = True
                if self.prefill_chunk:
                    break
            self._count_deferrals(rs)
            if did_prefill and pre_active:
                m.inc("interleaved_steps_total")

            if not sched.active:
                self._poll_compiles(rs)
                tr.end()                  # step
                self._drain_stream(rs)
                yield
                continue

            # -- one ragged decode step over the whole pool ----------------
            for slot, st in sched.active.items():
                feed[slot] = st.next_token
                pos[slot] = st.pos
            t0 = perf_counter()
            try:
                with tr.span("dispatch"):
                    self._fault_dispatch(rs)
                    logits, pool, dstats = self._decode(
                        self.params, pool,
                        {"tokens": jnp.asarray(feed[:, None])},
                        jnp.asarray(pos))
                    rs.rng, sub = jax.random.split(rs.rng)
                    tok_dev = sample(logits, sub, self.temperature)
            except FaultInjected:
                # raised before the jitted call: pool untouched, no token
                # lost — abandon the iteration and re-plan (the retry path
                # a real transient dispatch failure would take)
                m.inc("dispatch_retries_total")
                self._poll_compiles(rs)
                tr.end()                  # step
                self._drain_stream(rs)
                yield
                continue
            m.inc("decode_dispatches_total")
            t_sync = perf_counter()
            with tr.span("sync"):
                self._fault_stall(rs)
                toks = np.asarray(tok_dev)
                gates = (np.asarray(dstats["attn_gate"], np.float32)
                         if "attn_gate" in dstats else None)
            now = perf_counter()
            m.inc("device_seconds_total", now - t_sync)
            step_s = now - t0
            m.inc("decode_seconds_total", step_s)
            m.observe("step_seconds", step_s)
            self._watch(rs, "decode_step", step_s)

            with tr.span("bookkeep"):
                cur = list(sched.active)
                if tr.enabled:
                    t0u, t1u = tr.to_us(t0), tr.to_us(now)
                    for slot in cur:
                        tr.span_at(f"decode[{rs.disp_idx}]",
                                   request_tid(sched.active[slot].req.uid),
                                   t0u, t1u, tokens=1)
                lay = (gates[:, cur].mean(axis=1) if gates is not None
                       else None)
                for slot in cur:
                    st = sched.active[slot]
                    g = gates[:, slot] if gates is not None else None
                    if g is not None:
                        rs.keep_acc += float(g.sum())
                        rs.keep_n += L_attn
                    reason = self._advance_slot(rs, st, int(toks[slot]), g,
                                                step_s, measure, L_attn)
                    if reason:
                        self._finish(rs, slot, reason)
                self._record_step_series(rs, lay)
            rs.step_idx += 1
            rs.disp_idx += 1
            self._poll_compiles(rs)
            tr.end()                      # step
            self._drain_stream(rs)
            yield

        m.inc("host_seconds_total",
              (perf_counter() - t_loop) - m.value("device_seconds_total"))
        return self._finalize(rs)

    def _finalize(self, rs: _RunState) -> Dict[str, object]:
        """Derive the run's ServeStats from the metrics registry (the flat
        dataclass is a *view* — every counter field reads out of the
        registry, which the returned dict carries too), fold per-request
        accounting into the aggregate KV numbers, and flush the trace."""
        stats, results, m = rs.stats, rs.results, rs.metrics
        stats.prefill_tokens = int(m.value("prefill_tokens_total"))
        stats.decode_tokens = int(m.value("decode_tokens_total"))
        stats.prefill_s = m.value("prefill_seconds_total")
        stats.decode_s = m.value("decode_seconds_total")
        stats.prefill_chunks = int(m.value("prefill_chunks_total"))
        stats.interleaved_steps = int(m.value("interleaved_steps_total"))
        stats.requests_completed = int(m.value("requests_completed_total"))
        stats.decode_dispatches = int(m.value("decode_dispatches_total"))
        stats.device_s = m.value("device_seconds_total")
        stats.host_s = m.value("host_seconds_total")
        stats.preemptions = int(m.value("preemptions_total"))
        stats.compiles = int(m.value("compiles_total"))
        stats.faults_injected = int(m.value("faults_injected_total"))
        stats.dispatch_retries = int(m.value("dispatch_retries_total"))
        stats.watchdog_strikes = int(m.value("watchdog_strikes_total"))
        stats.requests_cancelled = int(m.value("requests_cancelled_total"))
        stats.deadline_exceeded = int(m.value("deadline_exceeded_total"))
        stats.requests_shed = int(m.value("requests_shed_total"))
        stats.preempt_budget_exhausted = int(
            m.value("preempt_budget_exhausted_total"))
        stats.epoch_shrinks = int(m.value("epoch_shrinks_total"))
        stats.snapshots = int(m.value("snapshots_total"))
        stats.resumes = int(m.value("resumes_total"))
        stats.spec_windows = int(m.value("spec_windows_total"))
        stats.spec_tokens_drafted = int(m.value("spec_tokens_drafted_total"))
        stats.spec_tokens_accepted = int(
            m.value("spec_tokens_accepted_total"))
        stats.spec_entries_rolled_back = int(
            m.value("spec_entries_rolled_back_total"))
        if stats.spec_tokens_drafted:
            stats.spec_acceptance_rate = (stats.spec_tokens_accepted
                                          / stats.spec_tokens_drafted)
        stats.attn_keep_frac = (rs.keep_acc / rs.keep_n if rs.keep_n
                                else 1.0)
        tot_dense = sum(r.kv_dense for r in results.values())
        tot_stored = sum(r.kv_stored for r in results.values())
        stats.kv_saved_fraction = (1.0 - tot_stored / tot_dense
                                   if tot_dense else 0.0)
        stats.kv_saved_analytic = analytic_kv_saved(self.cfg)
        if self.kv_mode == "paged":
            alloc = self.allocator
            stats.pages_peak = alloc.stats.pages_peak
            stats.kv_entries_stored = alloc.stats.entries_appended
            stats.kv_entries_dense = alloc.stats.entries_dense
            stats.history_hit_rate = rs.hist.hit_rate
            stats.history_hits_per_layer = rs.hist.per_layer_hit_rate
            m.set("pages_peak", alloc.stats.pages_peak)
            for i, h in enumerate(rs.hist.per_layer_hit_rate):
                m.set("history_hit_rate", h, layer=i)
            if self.prefix is not None:
                stats.prefix_hits = int(m.value("prefix_hits_total"))
                stats.prefix_misses = int(m.value("prefix_misses_total"))
                stats.prefix_tokens_saved = int(
                    m.value("prefix_tokens_saved_total"))
                stats.prefix_records = len(self.prefix)
                m.set("prefix_records", len(self.prefix))
        if self.tracer.enabled and self.tracer.path is not None:
            self.tracer.save()
        return {"results": results, "stats": stats, "metrics": m}

    def _run_paged(self, rng: Optional[jax.Array] = None
                   ) -> Dict[str, object]:
        """Paged-pool mode: KV lives in the store-once entry stream
        (``repro/kvcache/paged.py``) with alloc-on-demand pages.

        Per iteration: (1) *proactively* guarantee one decode step of page
        headroom for every resident slot — preempting the youngest
        resident (requeued at the head of the FIFO) if the free list runs
        dry, so the step itself can never OOM; (2) consume one
        ``plan_step`` plan — admission is gated on genuinely spare pages
        via ``_can_place``, and at most one prefill work unit (a whole
        prompt, or one chunk with ``prefill_chunk > 0``) runs per
        iteration, the cadence this loop has always had; (3) one ragged
        decode step over all slots; (4) append the measured fresh entries
        and the history-buffer hit accounting from the returned gate log.
        """
        cfg = self.cfg
        sched = self.scheduler
        alloc = self.allocator
        nA = self.n_attn
        reuse = paged_mod.reuse_enabled(cfg)
        measure = cfg.skip.enabled and cfg.skip.kv_reuse
        rs = self._new_run_state(rng, paged=True)
        m, tr = rs.metrics, self.tracer

        store = self._apply_resume(rs, self._acquire_store())
        feed = np.zeros((self.max_slots,), np.int32)
        pos = np.zeros((self.max_slots,), np.int32)
        t_loop = perf_counter()

        while sched.has_work():
            self._boundary(rs, store)
            if not sched.has_work():      # lifecycle sweep drained the run
                break
            tr.begin("step", idx=rs.disp_idx)
            self._step_gauges(rs)
            # -- proactive headroom first: every resident can absorb one
            # full step before anyone new is let in (a newcomer admitted
            # into pages the residents need would be preempted right back,
            # throwing its prefill away)
            hidden = self._fault_oom(rs)
            with tr.span("headroom"):
                for slot in sorted(sched.active):
                    if slot not in sched.active:     # preempted below
                        continue
                    while not alloc.ensure(slot,
                                           int(alloc.fill[slot]) + nA):
                        if self._reclaim_pages():
                            continue
                        if not self._preempt_youngest(rs, exclude=slot):
                            if hidden:
                                # the injected OOM drove the pool all the
                                # way down to one resident; return the
                                # hidden pages instead of dying
                                alloc.unhide_pages(hidden)
                                hidden = []
                                continue
                            raise PageExhausted(
                                f"page pool exhausted with a single "
                                f"resident request (slot {slot}) — "
                                "submit() should have rejected it",
                                slot=slot, free_pages=alloc.free_pages,
                                pages_total=self.num_pages)
            if hidden:
                alloc.unhide_pages(hidden)

            # -- prefill work from the step planner: admission gated on
            # free pages, one work unit per iteration so each _can_place
            # check sees the pages the previous admission consumed
            pre_active = bool(sched.active)
            with tr.span("plan"):
                plan = sched.plan_step(can_place=self._can_place,
                                       token_budget=self.step_tokens)
            self._note_admission(rs)
            self._count_deferrals(rs)
            # reserve a newly admitted prompt's worst-case pages NOW,
            # inside the same iteration as its _can_place check: chunked
            # execution and budget deferrals can postpone the first
            # prefill work past intervening resident-headroom passes,
            # which would otherwise consume the very pages the admission
            # check counted as spare (ensure() is idempotent, so a
            # deferred prompt re-running this is a no-op)
            pf = sched.prefilling
            if (pf is not None and pf.done == 0
                    and (self.prefill_chunk
                         or self.step_tokens is not None)):
                if not alloc.ensure(pf.slot,
                                    pf.req.prompt_len * nA + nA):
                    raise RuntimeError(
                        "worst-case page reservation failed in the same "
                        "iteration as a successful _can_place admission "
                        "check — allocator bug")
            if plan.prefill is not None:
                with tr.span("prefill"):
                    store = self._prefill_work_paged(rs, plan.prefill,
                                                     store)
                if pre_active:
                    m.inc("interleaved_steps_total")

            if not sched.active:
                self._poll_compiles(rs)
                tr.end()                  # step
                self._drain_stream(rs)
                yield
                continue

            # -- one ragged decode step over the whole pool ----------------
            for slot, st in sched.active.items():
                feed[slot] = st.next_token
                pos[slot] = st.pos
            # bound the stream walk to the live chains instead of the
            # worst-case block-table width; power-of-two buckets keep the
            # number of compiled decode shapes logarithmic (the same
            # recompile-bounding trick as prefill length-bucketing)
            j_live = max(1, alloc.max_chain_pages())
            j_step = min(1 << (j_live - 1).bit_length(),
                         alloc.pages_per_slot)
            walk = self._walk_state(j_step)
            t0 = perf_counter()
            try:
                with tr.span("dispatch", n=1, **walk):
                    self._fault_dispatch(rs)
                    logits, store, dstats = self._decode_paged(
                        self.params, store,
                        {"tokens": jnp.asarray(feed[:, None])},
                        jnp.asarray(pos),
                        jnp.asarray(alloc.block_table[:, :j_step]),
                        jnp.asarray(alloc.fill))
                    rs.rng, sub = jax.random.split(rs.rng)
                    tok_dev = sample(logits, sub, self.temperature)
            except FaultInjected:
                # pre-dispatch raise: store and allocator untouched —
                # abandon the iteration and re-plan (see _run_dense)
                m.inc("dispatch_retries_total")
                self._poll_compiles(rs)
                tr.end()                  # step
                self._drain_stream(rs)
                yield
                continue
            m.inc("decode_dispatches_total")
            self._count_walk(rs, 1, j_step, walk["entries"],
                             walk["positions"])
            t_sync = perf_counter()
            with tr.span("sync"):
                self._fault_stall(rs)
                toks = np.asarray(tok_dev)
                gates = np.asarray(dstats["attn_gate"], np.float32)
            now = perf_counter()
            m.inc("device_seconds_total", now - t_sync)
            step_s = now - t0
            m.inc("decode_seconds_total", step_s)
            m.observe("step_seconds", step_s)
            self._watch(rs, "decode_step", step_s)

            with tr.span("bookkeep"):
                cur = list(sched.active)
                if tr.enabled:
                    t0u, t1u = tr.to_us(t0), tr.to_us(now)
                    for slot in cur:
                        tr.span_at(f"decode[{rs.disp_idx}]",
                                   request_tid(sched.active[slot].req.uid),
                                   t0u, t1u, tokens=1)
                lay = gates[:, cur].mean(axis=1)
                for slot in cur:
                    st = sched.active[slot]
                    g = gates[:, slot]
                    fresh_n = int(1 + (g[1:] > 0.5).sum()) if reuse else nA
                    alloc.append(slot, fresh_n, nA)
                    rs.hist.on_decode_step(slot, g)
                    rs.keep_acc += float(g.sum())
                    rs.keep_n += nA
                    reason = self._advance_slot(rs, st, int(toks[slot]), g,
                                                step_s, measure, nA)
                    if reason:
                        self._finish(rs, slot, reason)
                self._record_step_series(rs, lay)
            rs.step_idx += 1
            rs.disp_idx += 1
            self._poll_compiles(rs)
            tr.end()                      # step
            self._drain_stream(rs)
            yield

        m.inc("host_seconds_total",
              (perf_counter() - t_loop) - m.value("device_seconds_total"))
        self._store = store
        return self._finalize(rs)

    # -- speculative decoding (spec_k > 0; docs/speculative.md) ------------
    def _window_gamma(self) -> int:
        """Draft length for this window, clamped so (a) every active
        slot can hold the window's C = γ+1 KV writes within ``max_len``
        (a verify write past the last row would clamp back onto
        committed rows) and (b) the window is not all waste when every
        resident is nearly out of generation budget.  0 = verify-only:
        a C=1 window, i.e. exactly one plain decode step."""
        g = self.spec_k
        rem_max = 1
        for st in self.scheduler.active.values():
            g = min(g, self.max_len - st.pos - 1)
            rem_max = max(rem_max,
                          st.req.max_new_tokens - len(st.out_tokens))
        return max(0, min(g, rem_max - 1))

    def _override_drafts(self, feed: np.ndarray, dout) -> jnp.ndarray:
        """Apply the ``draft_override`` test hook: sync the draft
        tokens, let the hook rewrite each active slot's proposals, and
        rebuild the verify feed host-side (the extra sync is the hook's
        cost — it exists for forcing accept/reject patterns in tests,
        not for serving)."""
        d = np.asarray(dout["tokens"]).T.copy()              # [S, γ]
        for slot, st in self.scheduler.active.items():
            d[slot] = np.asarray(
                self.draft_override(st.req.uid, d[slot].copy()),
                np.int32)
        return jnp.asarray(np.concatenate([feed[:, None], d], axis=1))

    def _accept_windows(self, rs: _RunState, cur: List[int], gamma: int,
                        drafts: np.ndarray, tgt: np.ndarray,
                        vlog: Optional[np.ndarray],
                        dlog: Optional[np.ndarray]):
        """Host acceptance for one window.  Returns ({slot: emitted
        tokens (pre-truncation)}, {slot: accepted draft count}).
        Temperature 0 takes the greedy prefix-match path (the chain is
        then bit-identical to plain greedy decoding by induction);
        temperature > 0 runs the exact accept/resample test per slot
        with uniforms drawn from the run's rng stream, preserving the
        per-token emission distribution (serve/sampling.py)."""
        emitted: Dict[int, List[int]] = {}
        accepted: Dict[int, int] = {}
        if self.temperature <= 0.0:
            acc, corr = sampling_mod.greedy_verify(tgt, drafts)
            for slot in cur:
                a = int(acc[slot])
                emitted[slot] = ([int(x) for x in drafts[slot, :a]]
                                 + [int(corr[slot])])
                accepted[slot] = a
            return emitted, accepted
        S = drafts.shape[0]
        rs.rng, ka, kf = jax.random.split(rs.rng, 3)
        u_acc = np.asarray(jax.random.uniform(ka, (S, max(gamma, 1))),
                           np.float64)
        u_fin = np.asarray(jax.random.uniform(kf, (S, gamma + 1)),
                           np.float64)
        p_t = sampling_mod.softmax_probs(vlog, self.temperature)
        p_d = (sampling_mod.softmax_probs(dlog, self.temperature)
               if gamma else None)
        for slot in cur:
            if gamma:
                a, toks = sampling_mod.speculative_accept_window(
                    drafts[slot], p_d[slot], p_t[slot], u_acc[slot],
                    u_fin[slot])
            else:
                a, toks = 0, [sampling_mod.inverse_cdf_sample(
                    p_t[slot, 0], float(u_fin[slot, 0]))]
            emitted[slot] = toks
            accepted[slot] = a
        return emitted, accepted

    def _plan_emission(self, st: ActiveRequest,
                       toks: List[int]) -> List[int]:
        """Truncate a window's emitted tokens to what ``_advance_slot``
        will actually append — stop token, generation budget and pool
        ``max_len`` all end the request mid-window.  The paged engine
        commits exactly this many verify columns (the emitted chain's
        KV minus the final token, whose KV is written when it is fed as
        the next window's first column — the plain engine's fill
        trajectory, entry for entry)."""
        keep: List[int] = []
        for tok in toks:
            keep.append(tok)
            if st.req.stop_token is not None and tok == st.req.stop_token:
                break
            if len(st.out_tokens) + len(keep) >= st.req.max_new_tokens:
                break
            if st.pos + len(keep) >= self.max_len:
                break
        return keep

    def _emission_caps(self, cur: List[int]):
        """[S]-vector emission-truncation bounds for the fused commit —
        the device-side mirror of ``_plan_emission``'s loop bounds:
        per-slot generation budget, ``max_len`` headroom and stop token
        (-1 = none).  Inactive slots keep the harmless defaults (their
        committed count is masked to 0 by ``active``)."""
        S = self.max_slots
        budget = np.ones((S,), np.int32)
        length = np.ones((S,), np.int32)
        stop = np.full((S,), -1, np.int32)
        for s in cur:
            st = self.scheduler.active[s]
            budget[s] = st.req.max_new_tokens - len(st.out_tokens)
            length[s] = self.max_len - st.pos
            if st.req.stop_token is not None:
                stop[s] = st.req.stop_token
        return jnp.asarray(budget), jnp.asarray(length), jnp.asarray(stop)

    def _spec_bookkeep(self, rs: _RunState, cur: List[int], gamma: int,
                       plan_emit: Dict[int, List[int]],
                       accepted: Dict[int, int], gates: np.ndarray,
                       window_s: float, t0: float, now: float,
                       n_layers: int, measure: bool,
                       per_tok=None) -> int:
        """Walk each slot's (truncated) emission in token order, applying
        exactly the per-token accounting the plain loops do — emitted
        token i pairs with verify gate column i, the gates of processing
        the token that *produced* it, matching the single-step engines'
        (token, gate) pairing.  ``per_tok`` is the paged hook (allocator
        append + history replay).  Returns the longest emission (the
        window's step-equivalent count)."""
        m, tr, sched = rs.metrics, self.tracer, self.scheduler
        m.inc("spec_windows_total")
        max_emit = 1
        t0u = t1u = None
        if tr.enabled:
            t0u, t1u = tr.to_us(t0), tr.to_us(now)
        lay_sum, lay_n = None, 0
        for slot in cur:
            st = sched.active[slot]
            keep = plan_emit[slot]
            a = accepted[slot]
            tid = request_tid(st.req.uid)
            if gamma:
                m.inc("spec_tokens_drafted_total", gamma)
                m.inc("spec_tokens_accepted_total", a)
                m.observe("spec_acceptance_rate", a / gamma)
            tr.instant("accept", tid, drafted=gamma, accepted=a,
                       emitted=len(keep))
            if tr.enabled:
                tr.span_at(f"decode[{rs.disp_idx}]", tid, t0u, t1u,
                           tokens=len(keep))
            share = window_s / len(keep)
            max_emit = max(max_emit, len(keep))
            reason = None
            for i, tok in enumerate(keep):
                g = gates[:, slot, i] if gates is not None else None
                if g is not None:
                    rs.keep_acc += float(g.sum())
                    rs.keep_n += n_layers
                if per_tok is not None:
                    per_tok(slot, g)
                reason = self._advance_slot(rs, st, int(tok), g, share,
                                            measure, n_layers)
                if reason and i != len(keep) - 1:
                    raise RuntimeError(
                        f"speculative window divergence on slot {slot}: "
                        f"_advance_slot finished ({reason!r}) at emitted "
                        f"token {i} but _plan_emission kept {len(keep)} "
                        "— the truncation rules no longer mirror the "
                        "finish conditions")
            if gates is not None:
                win = gates[:, slot, :len(keep)].sum(axis=1)
                lay_sum = win if lay_sum is None else lay_sum + win
                lay_n += len(keep)
            if reason:
                self._finish(rs, slot, reason)
        self._record_step_series(
            rs, lay_sum / lay_n if lay_n else None)
        return max_emit

    def _run_dense_spec(self, rng: Optional[jax.Array] = None
                        ) -> Dict[str, object]:
        """Dense-pool speculative loop (``spec_k > 0``).

        Per iteration: admission/prefill exactly as ``_run_dense``, then
        ONE draft+verify window instead of a single decode step: (1) a
        γ-step draft loop under ``draft_params`` proposes tokens (KV
        writes tentative); (2) one ``verify_chunk`` dispatch runs the
        full model over [feed, drafts], rewriting every window row with
        the verifier's KV — dense rollback is free, rows beyond the
        accepted prefix stay dead until ``kv_valid_len`` reaches them
        and the next window overwrites them first; (3) a single sync
        pulls drafts, per-column verify argmax and gates; (4) the host
        accept/resample emits accepted prefix + correction per slot.
        Two dispatches per window, up to spec_k+1 tokens per slot;
        temperature-0 token output is bit-identical to ``_run_dense``."""
        cfg = self.cfg
        sched = self.scheduler
        rs = self._new_run_state(rng, paged=False)
        m, tr = rs.metrics, self.tracer
        L_attn = max(len(cfg.attention_layers), 1)
        measure = cfg.skip.enabled and cfg.skip.kv_reuse

        pool = init_pool(cfg, self.max_slots, self.max_len)
        if self.policy is not None:
            pool = jax.device_put(pool, self._pool_sh)
        pool = self._apply_resume(rs, pool)
        feed = np.zeros((self.max_slots,), np.int32)
        pos = np.zeros((self.max_slots,), np.int32)
        t_loop = perf_counter()

        while sched.has_work():
            self._boundary(rs, pool)
            if not sched.has_work():      # lifecycle sweep drained the run
                break
            tr.begin("step", idx=rs.disp_idx)
            self._step_gauges(rs)
            pre_active = bool(sched.active)
            did_prefill = False
            while True:
                with tr.span("plan"):
                    plan = sched.plan_step(token_budget=self.step_tokens)
                self._note_admission(rs)
                if plan.prefill is None:
                    break
                with tr.span("prefill"):
                    pool = self._prefill_work_dense(rs, plan.prefill, pool)
                did_prefill = True
                if self.prefill_chunk:
                    break
            self._count_deferrals(rs)
            if did_prefill and pre_active:
                m.inc("interleaved_steps_total")

            if not sched.active:
                self._poll_compiles(rs)
                tr.end()                  # step
                self._drain_stream(rs)
                yield
                continue

            # -- one draft+verify window over the whole pool ---------------
            cur = sorted(sched.active)
            for slot in cur:
                st = sched.active[slot]
                feed[slot] = st.next_token
                pos[slot] = st.pos
            gamma = self._window_gamma()
            t0 = perf_counter()
            try:
                feed_dev = jnp.asarray(feed)
                pos_dev = jnp.asarray(pos)
                dout = None
                with tr.span("draft", k=gamma):
                    self._fault_dispatch(rs)
                    if gamma:
                        pool, dout = self._spec_draft(gamma)(
                            self.draft_params, pool, feed_dev, pos_dev,
                            rs.rng)
                        rs.rng = dout["rng"]
                        feed_chunk = jnp.concatenate(
                            [feed_dev[:, None], dout["tokens"].T], axis=1)
                        if self.draft_override is not None:
                            feed_chunk = self._override_drafts(feed, dout)
                    else:
                        feed_chunk = feed_dev[:, None]
                with tr.span("verify", k=gamma):
                    tgt_dev, vlog_dev, pool, vstats = self._spec_verify()(
                        self.params, pool, {"tokens": feed_chunk}, pos_dev)
            except FaultInjected:
                # raised before the jitted calls: pool untouched — abandon
                # the window and re-plan (see _run_dense)
                m.inc("dispatch_retries_total")
                self._poll_compiles(rs)
                tr.end()                  # step
                self._drain_stream(rs)
                yield
                continue
            m.inc("decode_dispatches_total", 2 if gamma else 1)
            t_sync = perf_counter()
            with tr.span("sync"):
                self._fault_stall(rs)
                tgt = np.asarray(tgt_dev)                     # [S, C]
                drafts = np.asarray(feed_chunk[:, 1:])        # [S, γ]
                gates = (np.asarray(vstats["attn_gate"], np.float32)
                         if vstats.get("attn_gate") is not None else None)
                dlog = (np.asarray(dout["logits"]).transpose(1, 0, 2)
                        if (gamma and self.temperature > 0.0) else None)
                vlog = (np.asarray(vlog_dev)
                        if self.temperature > 0.0 else None)
            now = perf_counter()
            m.inc("device_seconds_total", now - t_sync)
            window_s = now - t0
            m.inc("decode_seconds_total", window_s)
            m.observe("step_seconds", window_s)
            self._watch(rs, "decode_window", window_s)

            with tr.span("bookkeep"):
                emitted, accepted = self._accept_windows(
                    rs, cur, gamma, drafts, tgt, vlog, dlog)
                plan_emit = {
                    s: self._plan_emission(sched.active[s], emitted[s])
                    for s in cur}
                max_emit = self._spec_bookkeep(
                    rs, cur, gamma, plan_emit, accepted, gates,
                    window_s, t0, now, L_attn, measure)
            rs.step_idx += max_emit
            rs.disp_idx += 1
            self._poll_compiles(rs)
            tr.end()                      # step
            self._drain_stream(rs)
            yield

        m.inc("host_seconds_total",
              (perf_counter() - t_loop) - m.value("device_seconds_total"))
        return self._finalize(rs)

    def _ensure_window(self, rs: _RunState, gamma: int,
                       hidden: List[int]) -> None:
        """Grow every active slot's page chain to the speculative
        window's worst case (fill + (γ+1)·n_attn entries) BEFORE the
        block table is snapshotted — device-side appends past the
        ensured chain would read block-table zeros and scatter into
        physical page 0, corrupting another slot's committed entries.
        Preempt-youngest backpressure mirrors ``_run_paged``'s per-step
        headroom pass; ``hidden`` is the oom-fault seam's page list,
        returned to the pool in place when it is the only way out."""
        alloc, sched = self.allocator, self.scheduler
        need_per = (gamma + 1) * self.n_attn
        for slot in sorted(sched.active):
            if slot not in sched.active:          # preempted below
                continue
            while not alloc.ensure(slot,
                                   int(alloc.fill[slot]) + need_per):
                if self._reclaim_pages():
                    continue
                if not self._preempt_youngest(rs, exclude=slot):
                    if hidden:
                        alloc.unhide_pages(hidden)
                        hidden.clear()
                        continue
                    raise PageExhausted(
                        f"page pool exhausted with a single resident "
                        f"request (slot {slot}) — submit() should have "
                        "rejected it", slot=slot,
                        free_pages=alloc.free_pages,
                        pages_total=self.num_pages)

    def _run_paged_spec(self, rng: Optional[jax.Array] = None
                        ) -> Dict[str, object]:
        """Paged-store speculative loop: ``_run_dense_spec``'s twin with
        the tentative-commit KV protocol (docs/speculative.md).

        Window anatomy: (1) resident window headroom is page-reserved
        up front (``_ensure_window``) — before admission, so
        ``_can_place`` sees the free list net of the residents' window,
        and again after admission so a newly activated request is
        covered too; (2) the draft loop appends *tentative* entries
        past the pre-window fill; (3) the verifier reads the committed
        prefix only (``in_fill`` masks at the pre-window fill) and
        returns every window column's full-model KV; (4) after the
        single sync and host acceptance, ``commit_verified`` rewrites
        the stream from the pre-window fill with exactly the emitted
        columns — in plain-engine token-major order — while the host
        replays the allocator/history accounting per emitted token and
        ``trim`` returns the rejected tail's pages.  Zero leaked pages,
        zero stale tentative entries (test_speculative.py pins both).

        At temperature 0 the verify and commit dispatches are FUSED
        (``_spec_verify_commit``): the device computes the greedy accept
        and the emission truncation itself and rewrites the stream in
        the verify dispatch, halving the per-window dispatch count; the
        host replays the acceptance from the synced argmax chain and
        asserts agreement.  Temperature > 0 keeps the two-phase path."""
        cfg = self.cfg
        sched = self.scheduler
        alloc = self.allocator
        nA = self.n_attn
        reuse = paged_mod.reuse_enabled(cfg)
        measure = cfg.skip.enabled and cfg.skip.kv_reuse
        rs = self._new_run_state(rng, paged=True)
        m, tr = rs.metrics, self.tracer
        fused = self.temperature <= 0.0

        store = self._apply_resume(rs, self._acquire_store())
        feed = np.zeros((self.max_slots,), np.int32)
        pos = np.zeros((self.max_slots,), np.int32)
        act = np.zeros((self.max_slots,), bool)
        t_loop = perf_counter()

        def per_tok(slot, g):
            fresh_n = int(1 + (g[1:] > 0.5).sum()) if reuse else nA
            alloc.append(slot, fresh_n, nA)
            rs.hist.on_decode_step(slot, g)

        while sched.has_work():
            self._boundary(rs, store)
            if not sched.has_work():      # lifecycle sweep drained the run
                break
            tr.begin("step", idx=rs.disp_idx)
            self._step_gauges(rs)
            # -- resident window headroom before admission (_can_place
            # must see the free list net of what residents need)
            hidden = self._fault_oom(rs)
            gamma = self._window_gamma() if sched.active else 0
            with tr.span("headroom"):
                self._ensure_window(rs, gamma, hidden)

            pre_active = bool(sched.active)
            with tr.span("plan"):
                plan = sched.plan_step(can_place=self._can_place,
                                       token_budget=self.step_tokens)
            self._note_admission(rs)
            self._count_deferrals(rs)
            pf = sched.prefilling
            if (pf is not None and pf.done == 0
                    and (self.prefill_chunk
                         or self.step_tokens is not None)):
                if not alloc.ensure(pf.slot,
                                    pf.req.prompt_len * nA + nA):
                    raise RuntimeError(
                        "worst-case page reservation failed in the same "
                        "iteration as a successful _can_place admission "
                        "check — allocator bug")
            if plan.prefill is not None:
                with tr.span("prefill"):
                    store = self._prefill_work_paged(rs, plan.prefill,
                                                     store)
                if pre_active:
                    m.inc("interleaved_steps_total")

            if not sched.active:
                if hidden:
                    alloc.unhide_pages(hidden)
                self._poll_compiles(rs)
                tr.end()                  # step
                self._drain_stream(rs)
                yield
                continue

            # -- final headroom pass: covers a request activated by this
            # iteration's prefill (idempotent for the residents), at the
            # final γ — which the newcomer's position may have clamped
            gamma = self._window_gamma()
            with tr.span("headroom"):
                self._ensure_window(rs, gamma, hidden)
            if hidden:
                alloc.unhide_pages(hidden)

            # -- one draft+verify window over the live chains --------------
            cur = sorted(sched.active)
            for slot in cur:
                st = sched.active[slot]
                feed[slot] = st.next_token
                pos[slot] = st.pos
            act[:] = False
            act[cur] = True
            fill0 = alloc.fill.copy()
            j_live = max(1, alloc.max_chain_pages())
            j_step = min(1 << (j_live - 1).bit_length(),
                         alloc.pages_per_slot)
            bt = jnp.asarray(alloc.block_table[:, :j_step])
            fill_dev = jnp.asarray(fill0)
            t0 = perf_counter()
            try:
                feed_dev = jnp.asarray(feed)
                pos_dev = jnp.asarray(pos)
                dout = None
                with tr.span("draft", k=gamma):
                    self._fault_dispatch(rs)
                    if gamma:
                        store, dout = self._spec_draft(gamma)(
                            self.draft_params, store, feed_dev, pos_dev,
                            fill_dev, jnp.asarray(act), rs.rng, bt)
                        rs.rng = dout["rng"]
                        feed_chunk = jnp.concatenate(
                            [feed_dev[:, None], dout["tokens"].T], axis=1)
                        if self.draft_override is not None:
                            feed_chunk = self._override_drafts(feed, dout)
                    else:
                        feed_chunk = feed_dev[:, None]
                with tr.span("verify", k=gamma):
                    if fused:
                        caps = self._emission_caps(cur)
                        store, tgt_dev, gates_dev, committed_dev = (
                            self._spec_verify_commit()(
                                self.params, store,
                                {"tokens": feed_chunk}, pos_dev, bt,
                                fill_dev, jnp.asarray(act), *caps))
                    else:
                        tgt_dev, vlog_dev, vstats = self._spec_verify()(
                            self.params, store, {"tokens": feed_chunk},
                            pos_dev, bt, fill_dev)
            except FaultInjected:
                # raised before the jitted calls: store and allocator
                # untouched beyond idempotent reservations — abandon the
                # window and re-plan (see _run_dense)
                m.inc("dispatch_retries_total")
                self._poll_compiles(rs)
                tr.end()                  # step
                self._drain_stream(rs)
                yield
                continue
            m.inc("decode_dispatches_total", 2 if gamma else 1)
            t_sync = perf_counter()
            with tr.span("sync"):
                self._fault_stall(rs)
                tgt = np.asarray(tgt_dev)                     # [S, C]
                drafts = np.asarray(feed_chunk[:, 1:])        # [S, γ]
                gates = np.asarray(
                    gates_dev if fused else vstats["attn_gate"],
                    np.float32)
                committed_np = (np.asarray(committed_dev) if fused
                                else None)
                dfill = (np.asarray(dout["fill"]) if gamma
                         else fill0)
                dlog = (np.asarray(dout["logits"]).transpose(1, 0, 2)
                        if (gamma and self.temperature > 0.0) else None)
                vlog = (np.asarray(vlog_dev)
                        if self.temperature > 0.0 else None)
            now = perf_counter()
            m.inc("device_seconds_total", now - t_sync)
            window_s = now - t0
            m.inc("decode_seconds_total", window_s)
            m.observe("step_seconds", window_s)
            self._watch(rs, "decode_window", window_s)

            with tr.span("bookkeep"):
                emitted, accepted = self._accept_windows(
                    rs, cur, gamma, drafts, tgt, vlog, dlog)
                plan_emit = {
                    s: self._plan_emission(sched.active[s], emitted[s])
                    for s in cur}
            with tr.span("rollback", k=gamma):
                committed = np.zeros((self.max_slots,), np.int32)
                for s in cur:
                    committed[s] = len(plan_emit[s])
                if fused:
                    # the device already committed inside the verify
                    # dispatch; the host replay must agree column-for-
                    # column or the entry stream is corrupt
                    if not np.array_equal(committed_np, committed):
                        raise RuntimeError(
                            "fused spec commit divergence: device "
                            f"committed {committed_np.tolist()} vs host "
                            f"plan {committed.tolist()} — greedy accept "
                            "replay bug")
                else:
                    bk, bv = vstats["kv_token"]
                    store, _ = self._spec_commit()(
                        store, bk, bv, vstats["attn_gate"], pos_dev, bt,
                        fill_dev, jnp.asarray(committed),
                        jnp.asarray(act))
                # rolled back = tentative draft entries the commit does
                # not cover (the draft's fresh counts come from the
                # *draft* gates, the commit's from the verifier's — with
                # full acceptance under an unbiased draft the rewrite
                # covers everything and this is 0)
                rolled = 0
                for s in cur:
                    cf = int(fill0[s])
                    for i in range(len(plan_emit[s])):
                        g = gates[:, s, i]
                        cf += (int(1 + (g[1:] > 0.5).sum())
                               if reuse else nA)
                    rolled += max(0, int(dfill[s]) - cf)
                m.inc("spec_entries_rolled_back_total", rolled)
                max_emit = self._spec_bookkeep(
                    rs, cur, gamma, plan_emit, accepted, gates,
                    window_s, t0, now, nA, measure, per_tok=per_tok)
                for slot in cur:
                    if slot in sched.active:
                        alloc.trim(slot)
            rs.step_idx += max_emit
            rs.disp_idx += 1
            self._poll_compiles(rs)
            tr.end()                      # step
            self._drain_stream(rs)
            yield

        m.inc("host_seconds_total",
              (perf_counter() - t_loop) - m.value("device_seconds_total"))
        self._store = store
        return self._finalize(rs)

    # -- fused-epoch run loops (decode_steps > 1) --------------------------
    def _epoch_args(self, rem: Dict[int, int]):
        """Build the device-loop batch arrays from the resident set.
        ``rem[slot]`` is filled with each slot's epoch horizon —
        min(budget remaining, positions to max_len) — whose max picks the
        epoch length.  Returns (feed, pos, act, budget, stop, slots)."""
        S = self.max_slots
        feed = np.zeros((S,), np.int32)
        pos = np.zeros((S,), np.int32)
        act = np.zeros((S,), bool)
        budget = np.zeros((S,), np.int32)
        stop = np.full((S,), -1, np.int32)
        slots = []
        for slot, st in self.scheduler.active.items():
            feed[slot] = st.next_token
            pos[slot] = st.pos
            act[slot] = True
            b = st.req.max_new_tokens - len(st.out_tokens)
            budget[slot] = b
            if st.req.stop_token is not None:
                stop[slot] = st.req.stop_token
            rem[slot] = min(b, self.max_len - st.pos)
            slots.append(slot)
        return feed, pos, act, budget, stop, slots

    def _epoch_len(self, rem: Dict[int, int]) -> int:
        """Epoch length: ``decode_steps`` clipped to the longest resident
        horizon, rounded up to a power of two so the lazily compiled loop
        variants stay logarithmic in N (the same recompile-bounding trick
        as prefill length-bucketing)."""
        rem_max = max(rem.values())
        return min(self.decode_steps,
                   1 << max(0, rem_max - 1).bit_length())

    def _process_epoch(self, rs: _RunState, out: Dict, slots: List[int],
                       t_disp: float, per_step=None) -> None:
        """Epoch sync + bookkeeping replay: pull the stacked (tokens,
        step_active, gates) off the device and walk them in step order,
        applying exactly the per-token accounting the single-step loops
        do — ``step_active`` masks the steps a slot sat out after
        finishing mid-epoch (its KV frozen on device), so emission sets
        match the single-step engine token for token.  ``per_step`` is
        the paged hook (allocator append + history replay).  A host/device
        divergence in finish detection raises instead of silently
        desyncing the KV state."""
        cfg, sched = self.cfg, self.scheduler
        m, tr = rs.metrics, self.tracer
        L_attn = max(len(cfg.attention_layers), 1)
        measure = cfg.skip.enabled and cfg.skip.kv_reuse
        t_sync = perf_counter()
        with tr.span("sync"):
            self._fault_stall(rs)
            toks = np.asarray(out["tokens"])                     # [n, S]
            step_act = np.asarray(out["step_active"])            # [n, S]
            gates = (np.asarray(out["attn_gate"], np.float32)
                     if out["attn_gate"] is not None else None)  # [n, L, S]
            fin_act = np.asarray(out["active"])
        now = perf_counter()
        m.inc("device_seconds_total", now - t_sync)
        epoch_s = now - t_disp
        m.inc("decode_seconds_total", epoch_s)
        m.observe("step_seconds", epoch_s)
        self._watch(rs, "decode_epoch", epoch_s)
        n_run = toks.shape[0]
        step_s = epoch_s / n_run

        with tr.span("bookkeep"):
            # deferred first tokens first: their slots either join the
            # epoch replay below (normal) or were entry-killed on device
            # and finish here with the stop reason (step_active all False)
            self._resolve_pending(rs)

            if tr.enabled:
                t0u, t1u = tr.to_us(t_disp), tr.to_us(now)
                for slot in slots:
                    st = sched.active.get(slot)
                    if st is not None:
                        tr.span_at(f"decode[{rs.disp_idx}]",
                                   request_tid(st.req.uid), t0u, t1u,
                                   tokens=int(step_act[:, slot].sum()))

            for slot in slots:
                st = sched.active.get(slot)
                if st is None:
                    continue  # entry-killed pending slot, finished above
                reason = None
                for s in range(n_run):
                    if not step_act[s, slot]:
                        continue
                    g = gates[s, :, slot] if gates is not None else None
                    if g is not None:
                        rs.keep_acc += float(g.sum())
                        rs.keep_n += L_attn
                    if per_step is not None:
                        per_step(slot, g)
                    reason = self._advance_slot(rs, st, int(toks[s, slot]),
                                                g, step_s, measure, L_attn)
                    if reason:
                        self._finish(rs, slot, reason)
                        break
                if (reason is None) != bool(fin_act[slot]):
                    raise RuntimeError(
                        f"fused-epoch divergence on slot {slot}: host "
                        f"finish reason {reason!r} vs device active "
                        f"{bool(fin_act[slot])} — the device loop's stop/"
                        "length conditions no longer mirror _advance_slot")

            lay = None
            if gates is not None:
                msum = float(step_act.sum())
                if msum:
                    # per-layer keep rate over every executed (step, slot)
                    lay = (gates * step_act[:, None, :]).sum(axis=(0, 2)) \
                        / msum
            self._record_step_series(rs, lay)
        rs.step_idx += n_run
        rs.disp_idx += 1

    def _run_dense_fused(self, rng: Optional[jax.Array] = None
                         ) -> Dict[str, object]:
        """Dense-pool loop with the device-resident N-step decode epoch
        (``decode_steps > 1``).  Per iteration: (1) dispatch one
        ``model.decode_loop`` epoch over the residents — sampling,
        stop/length detection and position advance all on device, the
        pool donated through the scan carry; (2) while that epoch is in
        flight, run the host's scheduling work — admission, prefill
        dispatches (first token sampled inside the prefill dispatch and
        left on device), pool inserts — none of which blocks; (3) sync
        once and replay the epoch's per-token bookkeeping.  Token output
        is identical to ``_run_dense`` at temperature 0."""
        cfg = self.cfg
        sched = self.scheduler
        rs = self._new_run_state(rng, paged=False)
        m, tr = rs.metrics, self.tracer

        pool = init_pool(cfg, self.max_slots, self.max_len)
        if self.policy is not None:
            pool = jax.device_put(pool, self._pool_sh)
        pool = self._apply_resume(rs, pool)
        t_loop = perf_counter()

        while sched.has_work():
            self._boundary(rs, pool)
            if not sched.has_work():      # lifecycle sweep drained the run
                break
            tr.begin("step", idx=rs.disp_idx)
            self._step_gauges(rs)
            # -- (1) dispatch one N-step epoch over the residents ----------
            out = None
            slots: List[int] = []
            n_eff = 1
            if sched.active:
                rem: Dict[int, int] = {}
                feed, pos, act, budget, stop, slots = self._epoch_args(rem)
                n_eff = self._epoch_len(rem)
                feed_dev = jnp.asarray(feed)
                for slot, tok_dev in rs.pending.items():
                    if act[slot]:
                        # deferred first token: overlay the device value
                        # into the feed carry (no host sync)
                        feed_dev = feed_dev.at[slot].set(tok_dev[0])
                t_disp = perf_counter()
                try:
                    with tr.span("dispatch", n=n_eff):
                        self._fault_dispatch(rs)
                        pool, out = self._dense_loop(n_eff)(
                            self.params, pool, feed_dev, jnp.asarray(pos),
                            jnp.asarray(act), jnp.asarray(budget),
                            jnp.asarray(stop), rs.rng)
                        rs.rng = out["rng"]
                except FaultInjected:
                    # pre-dispatch raise: pool untouched — abandon the
                    # epoch and re-plan (see _run_dense)
                    m.inc("dispatch_retries_total")
                    self._poll_compiles(rs)
                    tr.end()              # step
                    self._drain_stream(rs)
                    yield
                    continue
                m.inc("decode_dispatches_total")

            # -- (2) host scheduling work overlapping the in-flight epoch --
            pre_active = bool(sched.active)
            did_prefill = False
            with tr.span("plan"):
                while True:
                    plan = sched.plan_step(token_budget=self.step_tokens,
                                           decode_steps=n_eff)
                    self._note_admission(rs)
                    if plan.prefill is None:
                        break
                    with tr.span("prefill"):
                        pool = self._prefill_work_dense(rs, plan.prefill,
                                                        pool)
                    did_prefill = True
                    if self.prefill_chunk:
                        break
            self._count_deferrals(rs)
            if did_prefill and pre_active:
                m.inc("interleaved_steps_total")

            if out is None:
                self._poll_compiles(rs)
                tr.end()                  # step
                self._drain_stream(rs)
                yield
                continue

            # -- (3) one sync per epoch + bookkeeping replay ---------------
            self._process_epoch(rs, out, slots, t_disp)
            self._poll_compiles(rs)
            tr.end()                      # step
            self._drain_stream(rs)
            yield

        m.inc("host_seconds_total",
              (perf_counter() - t_loop) - m.value("device_seconds_total"))
        return self._finalize(rs)

    def _run_paged_fused(self, rng: Optional[jax.Array] = None
                         ) -> Dict[str, object]:
        """Paged-store loop with the device-resident N-step epoch
        (``model.paged_decode_loop``): the entry-stream fill advances on
        device, and the host replays the allocator/history accounting
        from the epoch's stacked gate log at the single sync.

        OOM safety moves from per-step to per-epoch granularity: before
        dispatch, every resident's worst case for the whole epoch
        (``fill + min(n_eff, horizon) × n_attn`` entries) is page-reserved
        up front.  If the free list can't cover it the epoch *shrinks*
        (halving ``n_eff``) before anyone is preempted — preemption
        (still youngest-first, requeued at the FIFO head) is the n_eff=1
        last resort, so backpressure costs epoch length before it costs
        a prefill."""
        cfg = self.cfg
        sched = self.scheduler
        alloc = self.allocator
        nA = self.n_attn
        reuse = paged_mod.reuse_enabled(cfg)
        rs = self._new_run_state(rng, paged=True)
        m, tr = rs.metrics, self.tracer

        store = self._apply_resume(rs, self._acquire_store())
        t_loop = perf_counter()
        walk = [0, 0]             # fills and positions the epoch's steps walk

        def per_step(slot, g):
            walk[0] += int(alloc.fill[slot])
            walk[1] += sched.active[slot].pos
            fresh_n = int(1 + (g[1:] > 0.5).sum()) if reuse else nA
            alloc.append(slot, fresh_n, nA)
            rs.hist.on_decode_step(slot, g)

        while sched.has_work():
            self._boundary(rs, store)
            if not sched.has_work():      # lifecycle sweep drained the run
                break
            tr.begin("step", idx=rs.disp_idx)
            self._step_gauges(rs)
            out = None
            slots: List[int] = []
            n_eff = 1
            if sched.active:
                rem: Dict[int, int] = {}
                for slot, st in sched.active.items():
                    rem[slot] = min(
                        st.req.max_new_tokens - len(st.out_tokens),
                        self.max_len - st.pos)
                n_eff = self._epoch_len(rem)
                if rs.epoch_cap:
                    # adaptive degradation: sustained page pressure left a
                    # cross-epoch cap; start from it instead of
                    # re-discovering the shrink every iteration
                    n_eff = min(n_eff, rs.epoch_cap)
                # epoch-granular headroom: shrink before preempting
                hidden = self._fault_oom(rs)
                shrunk = False
                with tr.span("headroom"):
                    while True:
                        failed = None
                        for slot in sorted(sched.active):
                            need = (int(alloc.fill[slot])
                                    + min(n_eff, rem.get(slot, 1)) * nA)
                            if not alloc.ensure(slot, need):
                                failed = slot
                                break
                        if failed is None:
                            break
                        if self._reclaim_pages():
                            continue
                        # hand back what the longer attempt reserved past
                        # each resident's fill before shrinking or evicting
                        for slot in sched.active:
                            alloc.trim(slot)
                        if n_eff > 1:
                            n_eff //= 2
                            shrunk = True
                            continue
                        # youngest-first over every resident, the failing
                        # one included: sparing it would evict an older
                        # request for a younger one, and two residents
                        # could then evict each other forever
                        if not self._preempt_youngest(
                                rs, exclude=(failed if len(sched.active) == 1
                                             else None)):
                            if hidden:
                                alloc.unhide_pages(hidden)
                                hidden = []
                                continue
                            raise PageExhausted(
                                f"page pool exhausted with a single "
                                f"resident request (slot {failed}) — "
                                "submit() should have rejected it",
                                slot=failed, free_pages=alloc.free_pages,
                                pages_total=self.num_pages)
                if hidden:
                    alloc.unhide_pages(hidden)
                if shrunk:
                    # remember the length that fit; grow back only after
                    # consecutive clean epochs (hysteresis, so a storm
                    # doesn't thrash shrink/grow every iteration)
                    rs.epoch_cap = n_eff
                    rs.clean_epochs = 0
                    m.inc("epoch_shrinks_total")
                    tr.instant("epoch_shrink", n_eff=n_eff)
                elif rs.epoch_cap:
                    rs.clean_epochs += 1
                    if rs.clean_epochs >= 2:
                        grown = rs.epoch_cap * 2
                        rs.epoch_cap = (0 if grown >= self.decode_steps
                                        else grown)
                        rs.clean_epochs = 0
                feed, pos, act, budget, stop, slots = self._epoch_args({})
                j_live = max(1, alloc.max_chain_pages())
                j_step = min(1 << (j_live - 1).bit_length(),
                             alloc.pages_per_slot)
                t_disp = perf_counter()
                try:
                    with tr.span("dispatch", n=n_eff,
                                 **self._walk_state(j_step)):
                        self._fault_dispatch(rs)
                        store, out = self._paged_loop(n_eff)(
                            self.params, store, jnp.asarray(feed),
                            jnp.asarray(pos), jnp.asarray(alloc.fill),
                            jnp.asarray(act), jnp.asarray(budget),
                            jnp.asarray(stop), rs.rng,
                            jnp.asarray(alloc.block_table[:, :j_step]))
                        rs.rng = out["rng"]
                except FaultInjected:
                    # pre-dispatch raise: store/allocator untouched —
                    # abandon the epoch and re-plan (see _run_dense)
                    m.inc("dispatch_retries_total")
                    self._poll_compiles(rs)
                    tr.end()              # step
                    self._drain_stream(rs)
                    yield
                    continue
                m.inc("decode_dispatches_total")

            # -- host scheduling work overlapping the in-flight epoch ------
            # (admission sees the free list net of the epoch reservation,
            # preserving the same-iteration _can_place invariant)
            pre_active = bool(sched.active)
            with tr.span("plan"):
                plan = sched.plan_step(can_place=self._can_place,
                                       token_budget=self.step_tokens,
                                       decode_steps=n_eff)
            self._note_admission(rs)
            self._count_deferrals(rs)
            pf = sched.prefilling
            if (pf is not None and pf.done == 0
                    and (self.prefill_chunk
                         or self.step_tokens is not None)):
                if not alloc.ensure(pf.slot,
                                    pf.req.prompt_len * nA + nA):
                    raise RuntimeError(
                        "worst-case page reservation failed in the same "
                        "iteration as a successful _can_place admission "
                        "check — allocator bug")
            if plan.prefill is not None:
                with tr.span("prefill"):
                    store = self._prefill_work_paged(rs, plan.prefill,
                                                     store)
                if pre_active:
                    m.inc("interleaved_steps_total")

            if out is None:
                self._poll_compiles(rs)
                tr.end()                  # step
                self._drain_stream(rs)
                yield
                continue

            walk[:] = [0, 0]
            self._process_epoch(rs, out, slots, t_disp, per_step=per_step)
            self._count_walk(rs, n_eff, j_step, *walk)
            self._poll_compiles(rs)
            tr.end()                      # step
            self._drain_stream(rs)
            yield

        m.inc("host_seconds_total",
              (perf_counter() - t_loop) - m.value("device_seconds_total"))
        self._store = store
        return self._finalize(rs)
