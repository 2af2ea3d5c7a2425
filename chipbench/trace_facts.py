#!/usr/bin/env python3
"""One traced run of a cell, and what its profiler trace says about the
engine's spans and the kernels' names.

    python3 chipbench/trace_facts.py --workload <cell> --seed <n> \
        --seconds <s> --out <dir>

Runs ``run.py`` with ``--trace 1`` in this process (its result line is
printed as usual), keeps the profiler trace under ``<dir>``, then reads
the trace and the engine's span tracer side by side and writes
``<dir>/trace_facts.json``:

- ``host_spans``: how many events of each engine-track span name the
  profiler's host planes hold in the window, and whether every
  ``dispatch`` lies inside a ``step``;
- ``clock``: the window's ``dispatch`` begins as the benchmark places
  them (the tracer's clock, shifted by the one offset taken where the
  window opens) against the profiler's own ``dispatch`` events, paired
  in order: count, largest and median gap in microseconds;
- ``kernels``: per Pallas call label in the device's ``XLA Ops`` line,
  device seconds, runs and the stats of one event, where a kernel name
  given to ``pallas_call`` would show;
- ``counters``: the window's increase of the engine's ``kv_walk`` and
  ``admission`` counters, by series;
- ``step_ends_s``: when each engine iteration ended, in seconds from
  the window's opening, from the first in the window to the first past
  its close (tokens are handed out at these instants, so how near the
  last one falls to the close says how near the window is to counting
  one epoch more or fewer).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import types

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

ENGINE_SPANS = ("step", "headroom", "plan", "prefill", "dispatch", "sync",
                "bookkeep", "snapshot", "draft", "verify")


def host_events(pd, names):
    """(name, start_ns, end_ns) of the host-plane events named in
    ``names``, in start order."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events if e.name in names]
    return sorted(out, key=lambda e: e[1])


def kernel_table(pd, lo, hi):
    """Pallas calls of the first chip's ``XLA Ops`` line in the window,
    by label: device seconds, runs, one event's text and stats."""
    from chipbench import trace as trace_mod

    table = {}
    for plane in pd.planes:
        if not trace_mod._DEVICE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != trace_mod.OPS_LINE:
                continue
            for e in line.events:
                if trace_mod.TPU_KERNEL not in e.name or not \
                        lo <= e.start_ns < hi:
                    continue
                row = table.setdefault(trace_mod.op_label(e.name), {
                    "seconds": 0.0, "runs": 0, "text": e.name[:400],
                    "stats": [[k, str(v)[:200]] for k, v in e.stats]})
                row["seconds"] += e.duration_ns * 1e-9
                row["runs"] += 1
        break
    return table


def clock_gaps(placed, profiled):
    """Pairs the two sorted begin lists in order; the largest and the
    median absolute gap in microseconds, and the shortest interval
    between two profiled begins (a pairing is unambiguous while the
    largest gap stays under half of it)."""
    gaps = sorted(abs(a - b) * 1e-3 for a, b in zip(placed, profiled))
    if not gaps:
        return {"paired": 0}
    return {"placed": len(placed), "profiled": len(profiled),
            "paired": len(gaps), "max_us": gaps[-1],
            "median_us": gaps[len(gaps) // 2],
            "mean_signed_us": sum((b - a) * 1e-3 for a, b in
                                  zip(placed, profiled)) / len(gaps),
            "min_interval_us": min(((b - a) * 1e-3 for a, b in
                                    zip(profiled, profiled[1:])),
                                   default=None)}


def step_ends(tracer, t_open, t_close):
    """Ends of the engine's ``step`` spans from ``t_open`` through the
    first one past ``t_close``, in seconds from ``t_open``."""
    lo, hi = tracer.to_us(t_open), tracer.to_us(t_close)
    out = []
    for ev in tracer.events:
        if (ev["ph"] == "E" and ev["name"] == "step" and ev.get("tid") == 0
                and ev["ts"] >= lo):
            out.append((ev["ts"] - lo) * 1e-6)
            if ev["ts"] > hi:
                break
    return out


def main(argv=None, require_chip: bool = True, root: pathlib.Path = ROOT
         ) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from chipbench import drive, engine_events
    from chipbench import run as harness
    from chipbench import trace as trace_mod

    out = pathlib.Path(args.out)
    keep = out / "xplane"
    seen = {}
    run_until, build = drive.ClosedLoop.run_until, drive.build

    def spy_window(loop, t_end):
        seen["t_close"] = t_end          # the window is [t_end - s, t_end]
        return run_until(loop, t_end)

    def spy_build(cell, seed, tracer=None):
        seen["tracer"] = tracer
        return build(cell, seed, tracer)

    drive.ClosedLoop.run_until, drive.build = spy_window, spy_build
    try:
        rc = harness.main(["--workload", args.workload, "--seed",
                           str(args.seed), "--seconds", str(args.seconds),
                           "--trace", "1", "--keep-trace", str(keep)],
                          require_chip=require_chip, root=root)
    finally:
        drive.ClosedLoop.run_until, drive.build = run_until, build
    tracer = seen.get("tracer")
    if rc or tracer is None or "t_close" not in seen:
        print("trace_facts: the traced run gave no tracer", file=sys.stderr)
        return rc or 1

    pd = trace_mod.load(str(keep))
    win = host_events(pd, {trace_mod.WINDOW})
    (_, lo, hi), = win
    t_open = seen["t_close"] - args.seconds
    window = types.SimpleNamespace(t_open=t_open, t_close=seen["t_close"])
    off = tracer.to_us(t_open)
    spans = [e for e in host_events(pd, set(ENGINE_SPANS))
             if lo <= e[1] <= hi]
    steps = [(s, e) for n, s, e in spans if n == "step"]
    dispatch = [(s, e) for n, s, e in spans if n == "dispatch"]
    nested = all(any(s0 <= s and e <= e0 for s0, e0 in steps)
                 for s, e in dispatch)
    placed = [lo + (ev["ts"] - off) * 1e3 for ev in tracer.events
              if ev["ph"] == "B" and ev["name"] == "dispatch"
              and ev.get("tid") == 0
              and lo <= lo + (ev["ts"] - off) * 1e3 <= hi]
    facts = {
        "workload": args.workload, "seed": args.seed,
        "window_s": (hi - lo) * 1e-9,
        "host_spans": {n: sum(1 for m, _, _ in spans if m == n)
                       for n in ENGINE_SPANS},
        "dispatch_inside_step": nested,
        "clock": clock_gaps(placed, [s for s, _ in dispatch]),
        "kernels": kernel_table(pd, lo, hi),
        "counters": {n: engine_events.counter_delta(window, n)
                     for n in ("kv_walk", "admission")},
        "step_ends_s": step_ends(tracer, t_open, seen["t_close"]),
    }
    (out / "trace_facts.json").write_text(json.dumps(facts, indent=1))
    print(f"trace_facts: host spans {json.dumps(facts['host_spans'])}; "
          f"dispatch inside step {nested}; clock "
          f"{json.dumps(facts['clock'])}; counters "
          f"{json.dumps(facts['counters'])}; step ends "
          f"{json.dumps(facts['step_ends_s'])}", flush=True)
    for label, row in sorted(facts["kernels"].items(),
                             key=lambda kv: -kv[1]["seconds"]):
        print(f"trace_facts: kernel {label!r}: {row['seconds']!r} s over "
              f"{row['runs']} runs; stats {json.dumps(row['stats'])}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
