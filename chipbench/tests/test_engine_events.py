"""The readers of the engine's own counters and spans, on a synthetic
tracer and window, and on a traced run of the tiny cell."""
import importlib.util
import json
import pathlib
import types

import pytest

from chipbench import engine_events
from chipbench.tests import tiny

METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"
ENGINE = ("paged_walk_useful_share", "paged_walk_live_share",
          "admission_deferred_per_epoch", "first_token_lag_p50_ms")


def metric(name):
    sp = importlib.util.spec_from_file_location(name, METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


def window(events):
    """A fresh tracer holding ``events`` (times in seconds from its
    start) and a context whose window runs from 1 s to 3 s."""
    from repro.obs import Tracer

    tr = Tracer()
    for ev in events:
        tr.events.append(dict(ev, ts=ev["ts"] * 1e6, pid=1))
    return types.SimpleNamespace(t_open=tr._t0 + 1.0, t_close=tr._t0 + 3.0)


def walk(ts, walked, live, valid):
    return {"name": "kv_walk", "ph": "C", "tid": 0, "ts": ts,
            "args": {"walked": walked, "live": live, "valid": valid}}


def admission(ts, one, pages):
    return {"name": "admission", "ph": "C", "tid": 0, "ts": ts,
            "args": {"one_per_iteration": one, "pages": pages}}


def span(name, ts, tid=0, ph="B"):
    return {"name": name, "ph": ph, "tid": tid, "ts": ts}


def test_walk_shares_are_window_deltas():
    ctx = window([walk(0.5, 100, 40, 2), walk(1.5, 300, 120, 6),
                  walk(2.5, 1100, 440, 22), walk(3.5, 9000, 9000, 9000)])
    # from the row before the window (0.5 s) to the last row in it
    assert metric("paged_walk_useful_share").read(ctx) == pytest.approx(2.0)
    assert metric("paged_walk_live_share").read(ctx) == pytest.approx(40.0)


def test_counter_delta_restarts_after_a_new_run():
    ctx = window([walk(0.5, 100, 40, 2), walk(1.5, 200, 80, 4),
                  walk(2.0, 50, 20, 1), walk(2.5, 250, 100, 5)])
    d = engine_events.counter_delta(ctx, "kv_walk")
    assert d == {"walked": 350.0, "live": 140.0, "valid": 7.0}


def test_admission_deferrals_per_dispatch():
    ctx = window([admission(0.9, 4, 0), span("dispatch", 0.95),
                  span("dispatch", 1.1), admission(1.2, 5, 0),
                  span("dispatch", 2.0), admission(2.1, 7, 3),
                  span("dispatch", 3.2), admission(3.3, 20, 20)])
    assert metric("admission_deferred_per_epoch").read(ctx) == \
        pytest.approx((3 + 3) / 2)


def test_first_token_lag_pairs_each_request_with_its_last_prefill():
    ctx = window([
        span("prefill", 0.8, tid=0), span("prefill", 0.9, tid=0, ph="E"),
        span("prefill", 0.7, tid=2, ph="E"),       # aborted by preemption
        span("prefill", 1.4, tid=2, ph="E"),
        {"name": "first_token", "ph": "i", "tid": 2, "ts": 1.45},
        span("prefill", 0.95, tid=3, ph="E"),
        {"name": "first_token", "ph": "i", "tid": 3, "ts": 1.05},
        {"name": "first_token", "ph": "i", "tid": 4, "ts": 1.5},  # no prefill
        span("prefill", 2.8, tid=5, ph="E"),
        {"name": "first_token", "ph": "i", "tid": 5, "ts": 3.2},  # after
    ])
    assert metric("first_token_lag_p50_ms").read(ctx) == pytest.approx(75.0)


def test_nothing_to_read_gives_none(monkeypatch):
    ctx = window([walk(0.5, 100, 40, 2), admission(0.5, 1, 0)])
    for name in ENGINE:
        assert metric(name).read(ctx) is None, name
    # a program without ``last_tracer``: no tracer, nothing read, no raise
    import repro.obs

    monkeypatch.delattr(repro.obs, "last_tracer")
    ctx = window([walk(1.5, 100, 40, 2), span("dispatch", 1.5)])
    assert engine_events.tracer() is None
    for name in ENGINE:
        assert metric(name).read(ctx) is None, name


def test_traced_tiny_run_reads_the_engine_metrics(tmp_path):
    root = tiny.make_root(tmp_path)
    rc, lines, _ = tiny.run(root, trace=1)
    assert rc == 0
    got = json.loads(lines[-1])["metrics"]
    for name in ENGINE:
        assert got[name]["value"] > 0, name
    assert got["paged_walk_useful_share"]["value"] \
        < got["paged_walk_live_share"]["value"] <= 100


def test_trace_facts_on_a_traced_tiny_run(tmp_path):
    """The CPU's profile has host planes only: the engine's spans are on
    them, each dispatch inside its step, and every dispatch the tracer
    records in the window is paired with one the profiler records (how
    far apart the benchmark's one offset places them depends on the
    shared CPU here, and is read on the chip)."""
    import contextlib
    import io

    from chipbench import trace_facts

    root = tiny.make_root(tmp_path / "checkout")
    with contextlib.redirect_stdout(io.StringIO()), \
            contextlib.redirect_stderr(io.StringIO()):
        rc = trace_facts.main(["--workload", tiny.CELL, "--seed", "7",
                               "--seconds", "2", "--out",
                               str(tmp_path / "facts")],
                              require_chip=False, root=root)
    assert rc == 0
    facts = json.loads((tmp_path / "facts" / "trace_facts.json").read_text())
    spans = facts["host_spans"]
    assert all(spans[n] > 0 for n in ("step", "headroom", "plan", "dispatch",
                                      "sync", "bookkeep"))
    assert facts["dispatch_inside_step"] is True
    clock = facts["clock"]
    assert clock["paired"] == clock["placed"] == clock["profiled"] \
        == spans["dispatch"]
    assert clock["max_us"] >= clock["median_us"] >= 0
    assert facts["kernels"] == {}
    walk = facts["counters"]["kv_walk"]
    assert 0 < walk["valid"] < walk["live"] < walk["walked"]
    assert set(facts["counters"]["admission"]) == {"one_per_iteration",
                                                   "pages"}
    ends = facts["step_ends_s"]
    assert ends == sorted(ends) and ends[0] >= 0 and ends[-1] > 2.0 > ends[-2]
