"""Median time from the end of a request's prefill to the hand-out of
its first token, over the first tokens handed out in the window: the
engine tracer's request-track ``prefill`` span and ``first_token``
instant."""
import numpy as np

from chipbench import engine_events


def read(ctx):
    got = engine_events.window_events(ctx)
    if got is None:
        return None
    events, lo, hi = got
    prefilled, lags = {}, []
    for ev in events:
        tid = ev.get("tid", 0)
        if not tid:
            continue
        if ev["ph"] == "E" and ev["name"] == "prefill":
            prefilled[tid] = ev["ts"]
        elif (ev["ph"] == "i" and ev["name"] == "first_token"
              and lo <= ev["ts"] <= hi and tid in prefilled):
            lags.append(ev["ts"] - prefilled[tid])
    if not lags:
        return None
    return float(np.percentile(lags, 50)) * 1e-3
