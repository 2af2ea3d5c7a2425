"""The system under test and the closed loop that drives it.

The engine is ``repro``'s ``ContinuousBatchingEngine`` with the same
settings in every cell: paged KV of 512-entry bfloat16 pages, Pallas
kernels, int4 weights (group 128, power-of-two scales), fused decode
epochs of 8 greedy steps, monolithic prefill, no prefix cache, no
speculation.  The page count comes from the configuration file and
the slot count from the traffic mix (one slot per client).

One client per slot: a client submits its next request the moment its
last one finishes.  The harness steps the engine one iteration at a time
and stamps each token with the host clock of the iteration that hands
it out.  The engine has no public one-iteration step, so this uses its
private ``_pump`` and its token buffers (``_streams``); a request's end
and result come through the handle ``submit`` returns.  PERF.md lists
the public API a later change should add.

The warm-up serves the cell's own shapes (``warm_ladder``), then gives
each client a first request whose budget is a remainder drawn as the
closed loop's steady state leaves them (``loadgen.residual_budgets``),
so the window opens with the slots at staggered points of their
requests, not as a group that started together.
"""
from __future__ import annotations

import dataclasses
from time import perf_counter
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from chipbench import loadgen, spec

PAGE_SIZE = 512
DECODE_STEPS = 8


def build(cell: spec.Cell, seed: int, tracer=None):
    """(cfg, engine) for the cell, with weights drawn on the device from
    ``seed`` by the configuration's family (``families/``)."""
    import jax

    from repro.serve.config import (EngineConfig, KVConfig, ObsConfig,
                                    SchedulingConfig)
    from repro.serve.engine import ContinuousBatchingEngine

    eng_conf = cell.config["engine"]
    fam = cell.family
    cfg = dataclasses.replace(spec.model_config(cell.config, fam),
                              use_kernels=eng_conf.get("use_kernels", True))
    params = fam.program_params(spec.weights_key(seed),
                                fam.dims_of(cell.config))
    jax.block_until_ready(params)
    eng = ContinuousBatchingEngine(cfg, params, config=EngineConfig(
        kv=KVConfig(kv_mode="paged",
                    page_size=eng_conf.get("page_size", PAGE_SIZE),
                    num_pages=eng_conf["num_pages"]),
        scheduling=SchedulingConfig(
            max_slots=cell.mix["clients"],
            max_len=loadgen.max_len(cell.mix),
            prefill_chunk=0,
            decode_steps=eng_conf.get("decode_steps", DECODE_STEPS)),
        obs=ObsConfig(trace=tracer),
        temperature=0.0))
    return cfg, eng


def warm_ladder(eng, mix: dict, vocab: int, seed: int
                ) -> List[Tuple[np.ndarray, int]]:
    """Warm-up requests that reach every program shape the window can
    use, served before the traffic: for each prefill bucket the mix's
    prompts fall into, its shortest and its longest such prompt, then
    the longest prompt the engine holds.  Each has a budget of one
    decode epoch.  They are submitted in ascending order and admitted
    one an iteration, so the longest page chain, which sets a decode
    epoch's block-table width, passes through each length in turn."""
    lo, hi = mix["prompt_len"]
    ends: Dict[int, List[int]] = {}
    for n in range(lo, hi + 1):
        e = ends.setdefault(eng.scheduler.bucket_for(n), [n, n])
        e[1] = n
    budget = eng.decode_steps + 1
    lens = sorted({n for e in ends.values() for n in e}
                  | {eng.max_len - budget})
    rng = np.random.default_rng([seed, 3])
    return [(rng.integers(0, vocab, n, dtype=np.int32), budget)
            for n in lens]


@dataclasses.dataclass
class Req:
    uid: int
    client: int
    prompt: np.ndarray
    max_new: int
    t_submit: float
    handle: object = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    times: List[float] = dataclasses.field(default_factory=list)
    t_done: Optional[float] = None
    reason: Optional[str] = None
    kv_stored: Optional[int] = None     # KV entries the engine stored


@dataclasses.dataclass
class Snapshot:
    """Engine state before one iteration, and what the iteration did."""
    t0: float
    t1: float
    residents: int
    ctx_sum: int            # positions held by the residents
    entries: int            # KV entries stored for the residents
    dispatched: bool        # a decode epoch was dispatched


class ClosedLoop:
    def __init__(self, eng, clients: int):
        self.eng = eng
        self.clients = clients
        self.reqs: Dict[int, Req] = {}
        self.live: Dict[int, Req] = {}
        self.pool: Optional[loadgen.RequestPool] = None
        self.first_budget: Dict[int, int] = {}
        self.ladder: Dict[int, List] = {}
        self.first_req: Dict[int, Req] = {}
        self.submitting = True
        self.pumped = False
        self.snaps: List[Snapshot] = []
        self.rejected = 0

    def submit(self, client: int, prompt=None, max_new=None) -> None:
        """The client's next request: the given one, else the next of
        its warm-up requests, else the pool's next, with the client's
        first budget in place of the pool's output length the first
        time."""
        from repro.serve.errors import AdmissionRejected

        if prompt is None and self.ladder.get(client):
            prompt, max_new = self.ladder[client].pop(0)
        traffic = prompt is None
        if traffic:
            prompt, max_new = self.pool.next()
            max_new = self.first_budget.pop(client, max_new)
        try:
            h = self.eng.submit(prompt, max_new_tokens=max_new)
        except AdmissionRejected:
            self.rejected += 1
            return
        uid = int(h)
        tr = self.eng.tracer
        if tr.enabled and self.pumped:
            # a run in progress opens the request's lifecycle spans only
            # for requests queued when it started; open them for one
            # submitted mid-run, as the engine's admission closes them
            from repro.obs.trace import request_tid
            tr.begin("request", request_tid(uid))
            tr.begin("queued", request_tid(uid))
        r = Req(uid, client, prompt, max_new, perf_counter(), h)
        self.reqs[uid] = r
        self.live[uid] = r
        if traffic:
            self.first_req.setdefault(client, r)

    def start(self, pool, ladder=(), first_budgets=()) -> None:
        """The ``ladder`` (prompt, budget) pairs dealt to the clients in
        turn, then every client's first request from the pool, with
        budget ``first_budgets[client]``."""
        self.pool = pool
        self.first_budget = dict(enumerate(first_budgets))
        self.ladder = {}
        for i, pair in enumerate(ladder):
            self.ladder.setdefault(i % self.clients, []).append(pair)
        for c in range(self.clients):
            self.submit(c)

    def pump(self) -> None:
        eng = self.eng
        active = eng.scheduler.active
        n0 = eng.metrics.value("decode_dispatches_total") \
            if eng.metrics is not None else 0.0
        t0 = perf_counter()
        snap = (len(active), int(sum(st.pos for st in active.values())),
                int(sum(int(eng.allocator.fill[s]) for s in active)))
        if not eng._pump():
            raise RuntimeError("the engine drained: every client stopped")
        self.pumped = True
        t = perf_counter()
        self.snaps.append(Snapshot(
            t0, t, *snap,
            eng.metrics.value("decode_dispatches_total") > n0))
        for uid, r in list(self.live.items()):
            buf = eng._streams.get(uid, ())
            if len(buf) > len(r.tokens):
                new = [tok for tok, _ in buf[len(r.tokens):]]
                r.tokens += new
                r.times += [t] * len(new)
            if r.handle.done():
                res = r.handle.result()
                r.t_done, r.reason = t, res.finish_reason
                r.kv_stored = int(res.kv_stored)
                if list(np.asarray(res.tokens)) != r.tokens:
                    raise RuntimeError(f"request {uid}: streamed tokens "
                                       "differ from its result")
                del self.live[uid]
                if self.submitting:
                    self.submit(r.client)

    def run_until(self, t_end: float) -> None:
        while perf_counter() < t_end:
            self.pump()

    def run_while(self, cond: Callable[[], bool]) -> None:
        while cond():
            self.pump()

    def traffic_started(self) -> bool:
        """Every client's first traffic request has its first token."""
        return (len(self.first_req) == self.clients
                and all(r.tokens for r in self.first_req.values()))


def window_stats(loop: ClosedLoop, t_open: float, t_close: float) -> dict:
    """The end-to-end numbers of one window, from the harness's own
    timestamps.  Tokens come out a decode epoch at a time, so the rate
    is taken between hand-out instants: the tokens handed out after the
    window's first instant, over the time from its first to its last."""
    secs = t_close - t_open
    out_tokens = 0
    stamps: List[float] = []
    gaps: List[float] = []
    ttft: List[float] = []
    prompts: List[int] = []
    outputs: List[int] = []
    attempted = failed = 0
    for r in loop.reqs.values():
        inside = [t for t in r.times if t_open <= t <= t_close]
        out_tokens += len(inside)
        stamps += inside
        for a, b in zip(r.times, r.times[1:]):
            if t_open <= a and b <= t_close:
                gaps.append(b - a)
        if t_open <= r.t_submit < t_close:
            attempted += 1
            prompts.append(len(r.prompt))
            outputs.append(r.max_new)
            first = r.times[0] if r.times else None
            if first is not None and first <= t_close:
                ttft.append(first - r.t_submit)
            else:
                ttft.append(t_close - r.t_submit)
            if r.reason is not None and r.reason not in ("length", "stop"):
                failed += 1
    failed += loop.rejected
    rate = None
    if stamps:
        t_first, t_last = min(stamps), max(stamps)
        if t_last > t_first:
            rate = sum(t > t_first for t in stamps) / (t_last - t_first)
    return {
        "seconds": secs, "output_tokens": out_tokens,
        "output_tok_s": rate,
        "handout_span_s": (max(stamps) - min(stamps)) if stamps else 0.0,
        "itl_p95_ms": (float(np.percentile(gaps, 95)) * 1e3
                       if gaps else None),
        "itl_count": len(gaps),
        "ttft_p90_ms": (float(np.percentile(ttft, 90)) * 1e3
                        if ttft else None),
        "ttft_p50_ms": (float(np.percentile(ttft, 50)) * 1e3
                        if ttft else None),
        "ttft_count": len(ttft),
        "attempted": attempted, "failed": failed,
        "mean_prompt": float(np.mean(prompts)) if prompts else None,
        "mean_output": float(np.mean(outputs)) if outputs else None,
        "finished": sum(1 for r in loop.reqs.values()
                        if r.t_done is not None
                        and t_open <= r.t_done <= t_close),
    }
