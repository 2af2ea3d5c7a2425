"""The engine's own events in a traced window: the span tracer a run
records with, reached through ``repro.obs.last_tracer`` (the metric
readers get no tracer in their context), and what its counter rows and
spans say between the window's bounds.  A program without
``last_tracer`` gives no tracer, and every reader built on this module
then reads nothing."""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple


def tracer():
    """The most recently built enabled tracer of this process, or None."""
    try:
        from repro.obs import last_tracer
    except ImportError:
        return None
    return last_tracer()


def window_events(ctx) -> Optional[Tuple[List[dict], float, float]]:
    """The tracer's events and the window's bounds on its clock, or
    None without a tracer."""
    tr = tracer()
    if tr is None:
        return None
    return tr.events, tr.to_us(ctx.t_open), tr.to_us(ctx.t_close)


def counter_delta(ctx, name: str) -> Optional[Dict[str, float]]:
    """Increase over the window of each series of the engine-track
    counter ``name``, whose rows carry cumulative values: the sum of the
    steps between consecutive rows, from the last row before the window
    to the last row in it.  A value below its predecessor starts a new
    run's count from 0.  None when no row lies in the window."""
    got = window_events(ctx)
    if got is None:
        return None
    events, lo, hi = got
    prev: Dict[str, float] = {}
    total: Optional[Dict[str, float]] = None
    for ev in events:
        if ev["ph"] != "C" or ev["name"] != name or ev.get("tid") != 0:
            continue
        if ev["ts"] > hi:
            break
        vals = ev["args"]
        if ev["ts"] >= lo:
            total = total if total is not None else {}
            for k, v in vals.items():
                step = v - prev.get(k, 0.0)
                total[k] = total.get(k, 0.0) + (step if step >= 0 else v)
        prev = vals
    return total


def engine_spans(ctx, name: str) -> Optional[List[dict]]:
    """The begin events of the engine-track spans ``name`` opened in the
    window, or None without a tracer."""
    got = window_events(ctx)
    if got is None:
        return None
    events, lo, hi = got
    return [ev for ev in events
            if ev["ph"] == "B" and ev["name"] == name
            and ev.get("tid") == 0 and lo <= ev["ts"] <= hi]
