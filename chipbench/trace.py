"""Reduction of a profiler trace to device busy time, per-op and
per-program device time, and idle gaps labelled by the engine's host
spans.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  Each chip is a plane
``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per operation
run and ``XLA Modules`` one per program run.  The benchmark marks its
window with a host ``TraceAnnotation`` (``WINDOW``), whose start and
length put the window on the trace's clock.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "chipbench_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_SUFFIX = re.compile(r"[.\-_]?\d+$")
TPU_KERNEL = 'custom_call_target="tpu_custom_call"'
CONTAINERS = ("while", "conditional", "call")


def load(trace_dir: str):
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(paths[-1])


def opcode(text: str) -> str:
    """The opcode of an op event, whose name is its HLO instruction
    (``%fusion.12 = bf16[16,128]{...} fusion(...), kind=...``)."""
    rest = text.split(" = ", 1)[-1]
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rest[i + 1:].lstrip()
    else:
        rest = rest.split(" ", 1)[-1]
    return rest.split("(", 1)[0]


def is_container(text: str) -> bool:
    """Control flow whose device time is that of the ops inside it."""
    return opcode(text) in CONTAINERS


def op_label(text: str) -> str:
    """A short name for an op: a Pallas kernel's call site name or an
    op's instruction name, without the numeric suffix."""
    name = text.split(" = ", 1)[0].lstrip("%")
    if TPU_KERNEL in text:
        return "tpu_custom_call " + _SUFFIX.sub("", name)
    return _SUFFIX.sub("", name)


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, e.start_ns, e.duration_ns) for e in line.events]


def extract(pd) -> dict:
    """Plain lists from a ProfileData: the window marker's (start, end)
    in ns, and per chip its op and module events (name, start, dur)."""
    window = None
    chips = []
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            chip = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    chip["ops"] = _events(line)
                elif line.name == MODULES_LINE:
                    chip["modules"] = _events(line)
            chips.append(chip)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
    return {"window_ns": window, "chips": chips}


def _union(intervals: Iterable[Tuple[float, float]], lo: float, hi: float
           ) -> Tuple[float, List[Tuple[float, float]]]:
    """Length of the union of intervals clipped to [lo, hi], and the gaps
    between them there."""
    busy, gaps, cur = 0.0, [], lo
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s or e <= cur:
            continue
        if s > cur:
            gaps.append((cur, s))
            cur = s
        busy += e - cur
        cur = e
    if cur < hi:
        gaps.append((cur, hi))
    return busy, gaps


def reduce(ex: dict) -> Optional[dict]:
    """Busy and idle time of the window (the union of the ops that are
    not control flow), averaged over chips; device
    seconds per op group and per program (events that start in the
    window); the idle gaps of the first chip, in seconds from the
    window's start."""
    if ex["window_ns"] is None or not ex["chips"]:
        return None
    lo, hi = ex["window_ns"]
    busy, ops, modules, module_runs = [], {}, {}, {}
    gaps = []
    for i, chip in enumerate(ex["chips"]):
        b, g = _union(((s, s + d) for name, s, d in chip["ops"]
                       if not is_container(name)), lo, hi)
        busy.append(b)
        if i == 0:
            gaps = [((s - lo) * 1e-9, (e - s) * 1e-9) for s, e in g]
        for name, s, d in chip["ops"]:
            if lo <= s < hi and not is_container(name):
                ops[name] = ops.get(name, 0.0) + d * 1e-9
        for name, s, d in chip["modules"]:
            if lo <= s < hi:
                modules[name] = modules.get(name, 0.0) + d * 1e-9
                module_runs[name] = module_runs.get(name, 0) + 1
    n = len(ex["chips"])
    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy) * 1e-9 / n
    return {"window_s": window_s, "busy_s": busy_s,
            "idle_share": 1.0 - busy_s / window_s,
            "ops": ops, "modules": modules, "module_runs": module_runs,
            "gaps": gaps}


def seconds_of(table: Dict[str, float], names: Sequence[str]) -> float:
    """Total of the entries whose name contains any of ``names``."""
    return sum(v for k, v in table.items() if any(n in k for n in names))


def by_label(ops: Dict[str, float], label) -> Dict[str, float]:
    """Device seconds per ``label(op text)``."""
    out: Dict[str, float] = {}
    for k, v in ops.items():
        lab = label(k)
        out[lab] = out.get(lab, 0.0) + v
    return out


def engine_spans(events: Sequence[dict], offset_us: float
                 ) -> List[Tuple[float, float, str, int]]:
    """Closed engine-track spans of a ``repro`` Tracer as (start, end,
    name, depth) in seconds from the window's start (``offset_us``: the
    window's start on the Tracer's clock)."""
    out, stack = [], []
    for ev in events:
        if ev.get("tid") != 0:
            continue
        if ev["ph"] == "B":
            stack.append((ev["ts"], ev["name"]))
        elif ev["ph"] == "E" and stack:
            ts, name = stack.pop()
            out.append(((ts - offset_us) * 1e-6, (ev["ts"] - offset_us) * 1e-6,
                        name, len(stack)))
    return out


def label_gaps(gaps: Sequence[Tuple[float, float]],
               spans: Sequence[Tuple[float, float, str, int]]
               ) -> List[Tuple[str, float]]:
    """Each idle gap (start, length) named by the innermost engine span
    open at its midpoint, or ``harness`` outside every engine span.
    Spans of one depth never overlap, so each depth is searched by
    bisection."""
    levels: Dict[int, list] = {}
    for s, e, name, d in spans:
        levels.setdefault(d, []).append((s, e, name))
    for lv in levels.values():
        lv.sort()
    starts = {d: [s for s, _, _ in lv] for d, lv in levels.items()}
    out = []
    for start, length in gaps:
        mid = start + length / 2
        best = "harness"
        for d in sorted(levels, reverse=True):
            i = bisect.bisect_right(starts[d], mid) - 1
            if i >= 0 and levels[d][i][1] >= mid:
                best = levels[d][i][2]
                break
        out.append((best, length))
    return out
