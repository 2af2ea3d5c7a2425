"""Observability subsystem (``repro/obs``): metrics-registry semantics,
trace-schema validity, engine stats invariants, and cross-path metric
identity.

Acceptance bar: the registry is the run's source of truth and
``ServeStats`` a derived view over it, so (a) every counter field of the
stats dataclass must equal its registry reading, (b) count-valued
metrics (tokens, requests, chunks) must be identical across dense/paged
× single-step/fused on the same workload (wall-clock metrics obviously
differ), and (c) the emitted trace must be structurally valid Chrome
trace-event JSON — every ``B`` matched by an ``E``, engine phase spans
nested under their ``step``, loadable by ``tools/trace_summary.py``.
"""
import dataclasses
import json
import os
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.routing import neutral_router_bias
from repro.models import model as M
from repro.obs import MetricsRegistry, NullTracer, Tracer, as_tracer
from repro.serve.engine import ContinuousBatchingEngine

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import trace_summary  # noqa: E402

KEY = jax.random.PRNGKey(0)


def _cfg(**over):
    cfg = get_config("llama2-7b").smoke()
    return dataclasses.replace(cfg, **over) if over else cfg


def _params(cfg):
    # neutral bias => the router skips, so gate-derived metrics (keep
    # rate, measured KV saving) are exercised, not identically 1.0/0.0
    return neutral_router_bias(M.init_params(KEY, cfg))


def _workload(cfg, n=6, seed=0):
    rng = np.random.default_rng(seed)
    lens = rng.integers(4, 12, size=n)
    return [rng.integers(0, cfg.vocab_size, (int(l),), dtype=np.int32)
            for l in lens]


def _run_engine(cfg, params, *, kv_mode="dense", decode_steps=None,
                trace=None, max_new=8, **kw):
    eng = ContinuousBatchingEngine(cfg, params, max_slots=3, max_len=48,
                                   kv_mode=kv_mode,
                                   decode_steps=decode_steps,
                                   trace=trace, **kw)
    for p in _workload(cfg):
        eng.submit(p, max_new_tokens=max_new)
    return eng, eng.run(KEY)


# ---------------------------------------------------------------------------
# MetricsRegistry unit semantics
# ---------------------------------------------------------------------------

def test_registry_counter_gauge_histogram_series():
    m = MetricsRegistry()
    m.inc("c", 2.0)
    m.inc("c", 3.0)
    assert m.value("c") == 5.0
    m.set("g", 7.0)
    m.set("g", 4.0)
    assert m.value("g") == 4.0 and m.peak("g") == 7.0
    for v in (0.001, 0.02, 5.0):
        m.observe("h", v)
    h = m.histogram("h")
    assert h.count == 3 and abs(h.sum - 5.021) < 1e-9
    m.record("s", 0, 0.5, layer=1)
    m.record("s", 1, 0.25, layer=1)
    assert m.series("s", layer=1) == [(0.0, 0.5), (1.0, 0.25)]
    assert m.series("s", layer=2) == []


def test_registry_labels_are_independent_series():
    m = MetricsRegistry()
    m.inc("tok", 1, layer=0)
    m.inc("tok", 2, layer=1)
    assert m.value("tok", layer=0) == 1 and m.value("tok", layer=1) == 2
    assert m.value("tok") == 0.0          # unlabeled child never written


def test_registry_kind_conflict_raises():
    m = MetricsRegistry()
    m.inc("x")
    with pytest.raises(ValueError):
        m.set("x", 1.0)


def test_registry_snapshot_and_prometheus_roundtrip():
    m = MetricsRegistry()
    m.inc("req_total", 3)
    m.set("depth", 2.0)
    m.observe("lat_seconds", 0.01, layer=1)
    m.record("keep", 0, 0.75, layer=0)
    snap = m.snapshot()
    json.loads(json.dumps(snap))                       # JSON-able
    assert snap["counters"]["req_total"][""] == 3
    assert snap["gauges"]["depth"][""]["max"] == 2.0
    prom = m.to_prometheus()
    assert "# TYPE req_total counter" in prom
    assert 'lat_seconds_bucket{layer="1",le="+Inf"} 1' in prom
    assert "req_total 3" in prom.splitlines()


# ---------------------------------------------------------------------------
# Tracer unit semantics
# ---------------------------------------------------------------------------

def test_tracer_balanced_spans_and_nesting():
    tr = Tracer()
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    tr.instant("mark", foo=1)
    assert tr.open_spans() == {}
    spans = trace_summary.pair_spans(tr.events)[0]
    by_name = {s["name"]: s for s in spans}
    assert by_name["inner"]["depth"] == 1
    assert by_name["outer"]["depth"] == 0
    assert by_name["outer"]["dur"] >= by_name["inner"]["dur"]


def test_tracer_unbalanced_end_raises():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        tr.end()


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs each enter
    and exit by name, in order."""

    def __init__(self):
        self.log = []

    def __call__(self, name, **kw):
        log = self.log

        class _Ann:
            def __enter__(self):
                log.append(("enter", name))
                return self

            def __exit__(self, *exc):
                log.append(("exit", name))

        return _Ann()


def test_null_tracer_records_nothing(monkeypatch):
    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    tr = as_tracer(None)
    assert isinstance(tr, NullTracer) and not tr.enabled
    with tr.span("x"):
        tr.instant("y")
        tr.begin("z")
        tr.end()
    assert tr.events == [] and tr.open_spans() == {}
    assert ann.log == []                  # no profiler annotation either


def test_engine_spans_mirror_into_profiler_annotations(monkeypatch):
    """Engine-track spans opened live open an annotation of the same
    name and close it at their end; request-track spans and spans
    emitted after the fact open none."""
    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    tr = Tracer()
    tr.begin("step", idx=0)
    with tr.span("dispatch", n=8):
        tr.begin("request", 5)
    with tr.span("sync"):
        pass
    tr.span_at("decode[0]", 0, 1.0, 2.0)
    tr.end(5)
    tr.end()
    assert ann.log == [("enter", "step"), ("enter", "dispatch"),
                       ("exit", "dispatch"), ("enter", "sync"),
                       ("exit", "sync"), ("exit", "step")]
    assert tr.open_spans() == {}


def test_as_tracer_path_roundtrip(tmp_path):
    out = tmp_path / "t.json"
    tr = as_tracer(str(out))
    assert tr.enabled and tr.path == out


# ---------------------------------------------------------------------------
# Engine stats invariants (derived-view + accounting consistency)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_mode,steps", [("dense", None), ("dense", 4),
                                           ("paged", None), ("paged", 4)])
def test_stats_invariants(kv_mode, steps):
    cfg = _cfg()
    _, out = _run_engine(cfg, _params(cfg), kv_mode=kv_mode,
                         decode_steps=steps)
    s, m, results = out["stats"], out["metrics"], out["results"]
    # decode_tokens == sum of per-request emitted tokens
    assert s.decode_tokens == sum(r.tokens.shape[0]
                                  for r in results.values())
    assert s.requests_completed == len(results)
    # wall-clock sanity: the device wait is part of the measured
    # prefill/decode wall time, and host bookkeeping is non-negative
    assert 0.0 <= s.device_s <= s.decode_s + s.prefill_s + 1e-6
    assert s.host_s >= 0.0
    # derived view: every counter field reads out of the registry
    assert s.decode_tokens == int(m.value("decode_tokens_total"))
    assert s.prefill_tokens == int(m.value("prefill_tokens_total"))
    assert s.decode_dispatches == int(m.value("decode_dispatches_total"))
    assert s.requests_completed == int(m.value("requests_completed_total"))
    assert s.preemptions == int(m.value("preemptions_total"))
    assert s.compiles == int(m.value("compiles_total")) and s.compiles > 0
    # distributions exist and count what the scalars count
    assert m.histogram("ttft_seconds").count == len(results)
    assert m.value("queue_depth") == 0.0          # drained at loop exit
    # telemetry series: per-layer keep rate + measured KV-saved fraction
    n_layers = len(cfg.attention_layers)
    assert len(m.series("attn_keep_rate", layer=n_layers - 1)) > 0
    ks = m.series("kv_saved_fraction")
    assert ks and all(0.0 <= v <= 1.0 for _, v in ks)


def test_cross_path_metric_identity():
    """Count-valued metrics must agree across dense/paged ×
    single-step/fused on one workload (same tokens in, same tokens out —
    only wall-clock and dispatch-granularity metrics may differ)."""
    cfg = _cfg()
    params = _params(cfg)
    runs = {}
    for kv_mode in ("dense", "paged"):
        for steps in (None, 4):
            _, out = _run_engine(cfg, params, kv_mode=kv_mode,
                                 decode_steps=steps)
            runs[(kv_mode, steps)] = out
    ref = runs[("dense", None)]["metrics"]
    for key, out in runs.items():
        m = out["metrics"]
        for name in ("decode_tokens_total", "prefill_tokens_total",
                     "requests_completed_total"):
            assert m.value(name) == ref.value(name), (key, name)
        # greedy token output identical too (the metric identity is not
        # coincidental — it is the same generation)
        for uid, r in ref_results(runs).items():
            np.testing.assert_array_equal(out["results"][uid].tokens, r)


def ref_results(runs):
    return {uid: r.tokens
            for uid, r in runs[("dense", None)]["results"].items()}


def test_preemption_requeue_consistency():
    """Forced paged preemption: the counter, the requeue, and the trace
    instants must all tell the same story, and every request still
    completes."""
    cfg = _cfg()
    tr = Tracer()
    eng = ContinuousBatchingEngine(cfg, _params(cfg), max_slots=2,
                                   max_len=48, kv_mode="paged",
                                   num_pages=18, page_size=8, trace=tr)
    for p in _workload(cfg, n=5):
        eng.submit(p, max_new_tokens=10)
    out = eng.run(KEY)
    s, m = out["stats"], out["metrics"]
    assert s.requests_completed == 5 == len(out["results"])
    preempt_events = [ev for ev in tr.events
                      if ev.get("ph") == "i" and ev["name"] == "preempt"]
    assert s.preemptions == int(m.value("preemptions_total")) \
        == len(preempt_events)
    assert tr.open_spans() == {}


# ---------------------------------------------------------------------------
# Trace schema validation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_mode,steps", [("dense", None), ("paged", 4)])
def test_trace_schema_valid(tmp_path, kv_mode, steps):
    cfg = _cfg()
    path = tmp_path / "trace.json"
    eng, out = _run_engine(cfg, _params(cfg), kv_mode=kv_mode,
                           decode_steps=steps, trace=str(path))
    assert path.exists()                  # auto-saved at _finalize
    events = trace_summary.load_events(str(path))
    data = json.loads(path.read_text())
    assert data["displayTimeUnit"] == "ms"
    # every span balanced, per track (raises on mismatch)
    spans = trace_summary.pair_spans(events)
    # engine phase spans nest under their step span
    for s in spans[trace_summary.ENGINE_TID]:
        if s["name"] == "step":
            assert s["depth"] == 0
        else:
            assert s["depth"] >= 1, s
    # request lifecycle: one root span per submitted request, with its
    # queued/prefill phases and decode epochs inside it
    names = trace_summary.track_names(events)
    req_tids = [t for t, n in names.items() if n.startswith("req ")]
    assert len(req_tids) == len(out["results"])
    for tid in req_tids:
        by = {}
        for s in spans[tid]:
            by.setdefault(s["name"], []).append(s)
        assert len(by["request"]) == 1
        root = by["request"][0]
        assert root["depth"] == 0
        for name, group in by.items():
            if name == "request":
                continue
            for s in group:
                assert s["ts"] >= root["ts"] - 1e-6
                assert s["ts"] + s["dur"] <= root["ts"] + root["dur"] + 1e-6
        assert any(n.startswith("decode[") for n in by)
    # the CLI consumes it end to end
    summary = trace_summary.summarize(events)
    assert summary["n_requests"] == len(out["results"])
    assert summary["n_steps"] > 0
    assert sum(int(c.get("n_new", 1)) for c in summary["compiles"]) \
        == out["stats"].compiles
    assert trace_summary.main([str(path), "--json"]) == 0


@pytest.mark.parametrize("kv_mode", ["dense", "paged"])
def test_spec_trace_schema(tmp_path, kv_mode):
    """Speculative runs must trace their window anatomy: draft/verify
    (and, paged, rollback) spans nested under the step span, one
    ``accept`` instant per slot-window on the request's track, and the
    roll-up ``tools/trace_summary.py`` builds from those instants must
    agree with the engine's own counters."""
    cfg = _cfg()
    path = tmp_path / "spec.json"
    eng, out = _run_engine(cfg, _params(cfg), kv_mode=kv_mode,
                           spec_k=4, trace=str(path))
    st, m = out["stats"], out["metrics"]
    events = trace_summary.load_events(str(path))
    spans = trace_summary.pair_spans(events)     # raises if unbalanced

    names = {s["name"] for s in spans[trace_summary.ENGINE_TID]}
    assert {"draft", "verify"} <= names
    if kv_mode == "paged":
        assert "rollback" in names
    for s in spans[trace_summary.ENGINE_TID]:
        if s["name"] in ("draft", "verify", "rollback"):
            assert s["depth"] >= 1               # inside its step span

    # ServeStats is a derived view over the registry for spec counters too
    assert st.spec_windows == int(m.value("spec_windows_total")) > 0
    assert st.spec_tokens_drafted == \
        int(m.value("spec_tokens_drafted_total"))
    assert st.spec_tokens_accepted == \
        int(m.value("spec_tokens_accepted_total"))
    assert st.spec_entries_rolled_back == \
        int(m.value("spec_entries_rolled_back_total"))

    # accept instants: one per slot-window, each on a request track,
    # totals matching the counters exactly
    track = trace_summary.track_names(events)
    accepts = [ev for ev in events
               if ev.get("ph") == "i" and ev.get("name") == "accept"]
    assert accepts
    assert all(track.get(ev.get("tid", 0), "").startswith("req ")
               for ev in accepts)
    assert len(accepts) >= st.spec_windows
    summary = trace_summary.summarize(events)
    spec = summary["speculative"]
    assert spec is not None
    assert spec["windows"] == len(accepts)
    assert spec["tokens_drafted"] == st.spec_tokens_drafted
    assert spec["tokens_accepted"] == st.spec_tokens_accepted
    assert spec["acceptance_rate"] == pytest.approx(st.spec_acceptance_rate)
    # emitted tokens counted by the instants == decode tokens generated
    # minus each request's first token (that one comes off the prefill
    # logits, before any speculative window runs)
    assert spec["tokens_emitted"] == \
        st.decode_tokens - st.requests_completed
    # the draft/verify phases are part of the accounted step breakdown
    assert summary["phase_us"].get("draft", 0) > 0
    assert summary["phase_us"].get("verify", 0) > 0
    assert trace_summary.main([str(path), "--json"]) == 0


def test_spec_trace_absent_without_speculation(tmp_path):
    """A plain run must not emit speculative schema elements — the
    summary's speculative section stays None."""
    cfg = _cfg()
    path = tmp_path / "plain.json"
    _run_engine(cfg, _params(cfg), trace=str(path))
    events = trace_summary.load_events(str(path))
    assert not any(ev.get("name") == "accept" for ev in events
                   if ev.get("ph") == "i")
    assert trace_summary.summarize(events)["speculative"] is None


def test_tracing_off_is_default_and_run_has_metrics():
    cfg = _cfg()
    eng, out = _run_engine(cfg, _params(cfg))
    assert isinstance(eng.tracer, NullTracer)
    assert eng.tracer.events == []
    assert out["metrics"] is eng.metrics  # registry still populated
    assert out["stats"].decode_tokens > 0
