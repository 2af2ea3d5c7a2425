"""The engine's own accounting of where the paged walk and admission
lose their time: the walk counters against a brute-force count from the
decode dispatches' inputs and outputs, admission deferrals on scripted
and page-starved queues, the ``first_token`` instant, TTFT from
submission, and the engine spans on the profiler's host plane."""
import glob
import os
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.routing import neutral_router_bias
from repro.models import model as M
from repro.obs import Tracer
from repro.obs.trace import request_tid
from repro.serve.config import (EngineConfig, KVConfig, ObsConfig,
                                SchedulingConfig)
from repro.serve.engine import ContinuousBatchingEngine

KEY = jax.random.PRNGKey(0)
WALK = "paged_walk_entries_total"
DEFER = "admissions_deferred_total"


@pytest.fixture(scope="module")
def model():
    cfg = get_config("llama2-7b").smoke()
    # neutral bias: the routers skip some layers, so a position's entries
    # at the layers differ from the dense count
    return cfg, neutral_router_bias(M.init_params(KEY, cfg))


def _engine(model, *, slots=3, steps=1, pages=64, page_size=8,
            max_len=48, trace=None):
    cfg, params = model
    return ContinuousBatchingEngine(cfg, params, config=EngineConfig(
        kv=KVConfig(kv_mode="paged", page_size=page_size, num_pages=pages),
        scheduling=SchedulingConfig(max_slots=slots, max_len=max_len,
                                    decode_steps=steps),
        obs=ObsConfig(trace=trace)))


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (n,), dtype=np.int32)
            for n in lens]


def _fresh(g, reuse, nA):
    """Entries one step appends for one slot: layer 0 always, a later
    layer when its gate executed (``PageAllocator`` accounting)."""
    return int(1 + (g[1:] > 0.5).sum()) if reuse else nA


# ---------------------------------------------------------------------------
# The paged walk
# ---------------------------------------------------------------------------

def _spy_single(eng, log):
    """Record each single-step paged dispatch's walk from its inputs:
    block-table width, and the fills and positions of the slots with a
    chain (the only ones the step commits for)."""
    inner = eng._decode_paged

    def spy(params, store, batch, pos, bt, fill):
        fill_h, pos_h = np.asarray(fill), np.asarray(pos)
        act = fill_h > 0
        log.append((1, bt.shape[1], int(fill_h[act].sum()),
                    int(pos_h[act].sum())))
        return inner(params, store, batch, pos, bt, fill)

    eng._decode_paged = spy


def _spy_fused(eng, log, reuse, nA):
    """Record each fused epoch's walk from the loop's inputs and stacked
    outputs alone: every slot active at step k sits at its entry
    position plus k, with its entry fill plus what its earlier steps
    appended."""
    make = eng._paged_loop

    def paged_loop(n):
        fn = make(n)

        def run(params, store, feed, pos, fill, act, budget, stop, rng, bt):
            store, out = fn(params, store, feed, pos, fill, act, budget,
                            stop, rng, bt)
            step_act = np.asarray(out["step_active"])         # [n, S]
            gates = np.asarray(out["attn_gate"], np.float32)  # [n, L, S]
            f = np.asarray(fill).astype(np.int64)
            p = np.asarray(pos).astype(np.int64)
            live = valid = 0
            stopped = False
            for k in range(n):
                a = step_act[k]
                live += int(f[a].sum())
                valid += int((p[a] + k).sum())
                f = f + np.array([_fresh(gates[k, :, s], reuse, nA)
                                  if a[s] else 0 for s in range(len(a))])
                if k and (step_act[k - 1] & ~a).any():
                    stopped = True
            log.append((n, bt.shape[1], live, valid, stopped))
            return store, out

        return run

    eng._paged_loop = paged_loop


@pytest.mark.parametrize("steps", [1, 4])
def test_walk_counters_equal_a_brute_force_count(model, steps):
    from repro.kvcache import paged as paged_mod

    cfg, _ = model
    eng = _engine(model, steps=steps)
    nA, S, P = eng.n_attn, eng.max_slots, eng.page_size
    log = []
    if steps == 1:
        _spy_single(eng, log)
    else:
        _spy_fused(eng, log, paged_mod.reuse_enabled(cfg), nA)
    # budgets that end slots at different steps of an epoch
    for p, new in zip(_prompts(cfg, [5, 11, 7, 9, 4]), [3, 10, 6, 13, 2]):
        eng.submit(p, max_new_tokens=new)
    out = eng.run(KEY)
    m = out["metrics"]
    assert log and len(log) == int(m.value("decode_dispatches_total"))
    walked = sum(nA * S * j * P * n for n, j, *_ in log)
    assert m.value(WALK, part="walked") == walked
    assert m.value(WALK, part="live") == nA * sum(e[2] for e in log)
    assert m.value(WALK, part="valid") == nA * sum(e[3] for e in log)
    assert 0 < m.value(WALK, part="valid") < m.value(WALK, part="live") \
        < walked
    if steps > 1:
        assert any(e[4] for e in log)     # a slot finished mid-epoch


def test_walk_trace_counter_and_dispatch_arguments(model):
    cfg, _ = model
    tr = Tracer()
    eng = _engine(model, steps=4, trace=tr)
    for p in _prompts(cfg, [6, 9, 12]):
        eng.submit(p, max_new_tokens=9)
    m = eng.run(KEY)["metrics"]
    rows = [e for e in tr.events if e["ph"] == "C" and e["name"] == "kv_walk"]
    assert len(rows) == int(m.value("decode_dispatches_total"))
    assert rows[-1]["args"] == {p: m.value(WALK, part=p)
                                for p in ("walked", "live", "valid")}
    disp = [e for e in tr.events if e["ph"] == "B" and e["name"] == "dispatch"]
    assert len(disp) == len(rows)
    first = disp[0]["args"]
    assert set(first) == {"n", "residents", "positions", "entries", "J"}
    # the first epoch's residents are the first prompt, prefilled alone
    assert first["residents"] == 1 and first["positions"] == 6
    assert 6 <= first["entries"] <= 6 * eng.n_attn and first["J"] >= 1


# ---------------------------------------------------------------------------
# Admission
# ---------------------------------------------------------------------------

def test_admission_deferrals_on_a_scripted_queue(model):
    """Two slots, three requests; ``can_place`` refuses the second
    request for two iterations, then lets it in."""
    cfg, _ = model
    tr = Tracer()
    eng = _engine(model, slots=2, trace=tr)
    for p in _prompts(cfg, [5, 6, 7]):
        eng.submit(p, max_new_tokens=12)
    blocked = [True]
    can_place = eng._can_place
    eng._can_place = lambda req: (not (blocked[0] and req.uid == 1)
                                  and can_place(req))
    m = None

    def pump():
        assert eng._pump()
        return eng.metrics

    m = pump()           # r0 admitted; r1, r2 queued, one slot free
    assert m.value(DEFER, reason="one_per_iteration") == 1
    assert m.value(DEFER, reason="pages") == 0
    m = pump()           # the head (r1) refused: one slot, two queued
    m = pump()
    assert m.value(DEFER, reason="pages") == 2
    blocked[0] = False
    m = pump()           # r1 admitted; no slot left for r2
    m = pump()
    assert m.value(DEFER, reason="one_per_iteration") == 1
    assert m.value(DEFER, reason="pages") == 2
    rows = [e["args"] for e in tr.events
            if e["ph"] == "C" and e["name"] == "admission"]
    assert rows == [{"one_per_iteration": 1.0, "pages": 0.0},
                    {"one_per_iteration": 1.0, "pages": 1.0},
                    {"one_per_iteration": 1.0, "pages": 2.0},
                    {"one_per_iteration": 1.0, "pages": 2.0},
                    {"one_per_iteration": 1.0, "pages": 2.0}]
    while eng._pump():
        pass


def test_page_starved_head_is_counted_as_pages(model):
    """A pool that holds the long prompt only when empty: while the short
    request decodes, its pages keep the long one at the queue's head."""
    cfg, _ = model
    probe = _engine(model, slots=2)
    nA, P = probe.n_attn, probe.page_size
    pages = -(-37 * nA // P)              # the long prompt's need, exactly
    eng = _engine(model, slots=2, pages=pages)
    short, long_ = _prompts(cfg, [4, 36])
    eng.submit(short, max_new_tokens=20)
    eng.submit(long_, max_new_tokens=2)
    out = eng.run(KEY)
    m = out["metrics"]
    assert all(r.finish_reason == "length" for r in out["results"].values())
    # iteration 1 admits the short request with the long one queued; the
    # short one decodes tokens 3..20 in iterations 2..19, each refusing
    # the long one; iteration 20 admits it with nothing left queued
    assert m.value(DEFER, reason="one_per_iteration") == 1
    assert m.value(DEFER, reason="pages") == 18


# ---------------------------------------------------------------------------
# First token and TTFT
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("steps,oom_at", [(1, 4), (4, 2)])
def test_first_token_once_per_request_after_its_prefill(model, steps,
                                                        oom_at):
    """Hiding every free page at one iteration evicts a resident that
    has already handed out its first token; it re-prefills, and its
    ``first_token`` instant still fires once."""
    from repro.serve.faults import Fault, as_fault_plan

    cfg, _ = model
    tr = Tracer()
    eng = _engine(model, slots=2, steps=steps, trace=tr)
    eng.faults = as_fault_plan([Fault("oom", step=oom_at, pages=0)])
    for p in _prompts(cfg, [4, 11, 7, 9, 5]):
        eng.submit(p, max_new_tokens=10)
    out = eng.run(KEY)
    assert out["stats"].preemptions == 1
    evicted = [e["tid"] for e in tr.events
               if e["ph"] == "i" and e["name"] == "preempt"]
    for uid in out["results"]:
        tid = request_tid(uid)
        evs = [e for e in tr.events if e.get("tid") == tid]
        names = [(e["ph"], e["name"]) for e in evs]
        assert names.count(("i", "first_token")) == 1, uid
        first = names.index(("i", "first_token"))
        end = names.index(("E", "prefill"))
        assert end < first and evs[end]["ts"] <= evs[first]["ts"]
        if tid in evicted:
            # handed out before the eviction, re-prefilled after it
            assert first < names.index(("i", "preempt"))
            assert names.count(("E", "prefill")) == 2


def test_ttft_counts_from_submission(model):
    cfg, _ = model
    eng = _engine(model, slots=2)
    for p in _prompts(cfg, [5, 8, 6]):
        eng.submit(p, max_new_tokens=3)
    time.sleep(0.25)
    out = eng.run(KEY)
    ttft = [r.ttft_s for r in out["results"].values()]
    assert len(ttft) == 3 and min(ttft) >= 0.25
    h = out["metrics"].histogram("ttft_seconds")
    assert h.count == 3 and h.sum == pytest.approx(sum(ttft))


# ---------------------------------------------------------------------------
# The profiler's host plane
# ---------------------------------------------------------------------------

def _host_events(model, tmp_path, trace):
    from jax.profiler import ProfileData

    cfg, _ = model
    eng = _engine(model, steps=4, trace=trace)
    for p in _prompts(cfg, [6, 9]):
        eng.submit(p, max_new_tokens=6)
    eng.run(KEY)               # compile outside the capture
    for p in _prompts(cfg, [6, 9], seed=1):
        eng.submit(p, max_new_tokens=6)
    with jax.profiler.trace(str(tmp_path)):
        eng.run(KEY)
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.end_ns) for e in line.events]
    return out


PHASES = ("step", "dispatch", "sync", "bookkeep")


def test_engine_spans_on_the_profilers_host_plane(model, tmp_path):
    evs = _host_events(model, tmp_path, Tracer())
    by = {}
    for name, s, e in evs:
        by.setdefault(name, []).append((s, e))
    assert all(by.get(n) for n in PHASES), sorted(by)
    for s, e in by["dispatch"]:
        assert any(s0 <= s and e <= e0 for s0, e0 in by["step"])


def test_untraced_engine_puts_no_span_on_the_host_plane(model, tmp_path):
    evs = _host_events(model, tmp_path, None)
    assert evs and not {n for n, _, _ in evs} & set(PHASES)


def test_last_tracer_is_the_newest_enabled_tracer():
    from repro.obs import NullTracer, as_tracer, last_tracer

    a = Tracer()
    assert last_tracer() is a
    b = as_tracer("unused.json")
    assert last_tracer() is b
    NullTracer()
    as_tracer(None)
    assert last_tracer() is b
