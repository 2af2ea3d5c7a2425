#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell (an entry of ``workloads`` in BENCHMARK.json) names a
configuration file and a traffic mix file; this harness finds both by
name.  The configuration file's ``family`` names the architecture
family (``chipbench/families/<family>.py``), which picks the served
weights, the plain reference and the counts the readers use.  In one
process it: refuses to start unless JAX's first device is a TPU and
there are as many as the cell asks for; builds the model (random int4
weights drawn on the device from ``--seed``) and the engine; serves
warm-up requests that reach every program shape the window can use,
then each client's first request, with a budget that staggers the slots
as a steady closed loop would (``drive.py``); once every client's first
request has its first token, measures ``--seconds`` of closed-loop
traffic; frees the program and checks a sample of the served tokens
against the plain reference (``check.py``).  With ``--trace 1`` the
window runs under the profiler and the engine's span tracer, and the
per-layer metrics (one reader per metric under ``chipbench/metrics/``)
come from that trace.

Facts go to earlier lines of standard output; the numbers compared go
to the last lines of standard error; the last line of standard output
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics with ``--trace 0``, its per-layer ones
with ``--trace 1``), ``device``, with ``--trace 1`` ``breakdown``, and
last ``compared``.

``--control 1`` puts the control in the program's place for the
comparison: the family's reference one precision step below the
configuration, read at the same prompts and served tokens.  Its
``correct`` has to come out false.  The benchmark's runs never pass it.
"""
from time import perf_counter

T_START = perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
if str(ROOT / "src") not in sys.path:
    sys.path.insert(1, str(ROOT / "src"))

from chipbench import check, drive, loadgen, peaks, spec  # noqa: E402
from chipbench import trace as trace_mod  # noqa: E402

TRACE_COUNTERS = ("kv_entries_dense_measured_total",
                  "kv_entries_stored_measured_total",
                  "preemptions_total", "epoch_shrinks_total")


def log(msg: str) -> None:
    print(f"chipbench: {msg}", flush=True)


class CompileMeter:
    """Seconds JAX spends compiling or loading programs from the
    persistent cache, how many, and how many the cache served."""

    def __init__(self):
        import jax

        self.seconds, self.programs, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration_secs
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def take(self):
        out = (self.seconds, self.programs, self.cache_hits)
        self.seconds, self.programs, self.cache_hits = 0.0, 0, 0
        return out


def enable_compile_cache() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` when set,
    else ``.jax_cache/`` at the checkout root (a fixed path: the path is
    part of the cache's key).  Every program is cached, however quick
    its compile, so a run's set-up repeats the same work."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def load_metric(root: pathlib.Path, name: str):
    """The reader module of a per-layer metric, found by its name."""
    path = root / "chipbench" / "metrics" / f"{name}.py"
    if not path.exists():
        return None
    return spec.load_module(path, f"chipbench_metric_{name}")


def cell_metrics(bench: dict, cell: str, kind: str) -> list:
    """The metrics of ``kind`` (end_to_end or per_layer) this cell
    reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def tracer_facts(tracer, t_open: float, t_close: float, snaps):
    """From the engine's span tracer: the decode epochs and prefills
    dispatched in the window (each epoch with the engine state before its
    iteration), and the queue waits of the requests admitted in it."""
    lo, hi = tracer.to_us(t_open), tracer.to_us(t_close)
    epochs, prefills, submit, waits = [], [], {}, []
    snaps = [s for s in snaps if s.dispatched]
    si = 0
    for ev in tracer.events:
        ts = ev.get("ts")
        if ev["ph"] == "i" and ev["name"] == "submit":
            submit[ev["tid"]] = ts
        elif ev["ph"] == "i" and ev["name"] == "admit":
            if lo <= ts <= hi and ev["tid"] in submit:
                waits.append((ts - submit[ev["tid"]]) * 1e-6)
        elif ev["ph"] == "B" and ev["name"] == "dispatch" and lo <= ts <= hi:
            t = ts * 1e-6 + (t_open - lo * 1e-6)
            while si + 1 < len(snaps) and snaps[si].t1 < t:
                si += 1
            s = snaps[si]
            epochs.append(types.SimpleNamespace(
                n=ev["args"]["n"], residents=s.residents,
                ctx_sum=s.ctx_sum, entries=s.entries))
        elif (ev["ph"] == "B" and ev["name"].startswith("prefill[")
              and lo <= ts <= hi):
            prefills.append(ev["args"]["tokens"])
    return epochs, prefills, waits


def traced_window(ctx, out: dict, root: pathlib.Path, bench: dict,
                  cell: str, trace_dir: str, keep: str, tracer,
                  bucket_for) -> None:
    """The per-layer metrics of a traced run into ``out``: the profiler
    trace reduced (``trace.py``), the engine tracer's epochs, prefills
    and queue waits, one reader per metric; and the breakdown of device
    time by op and of idle gaps by the host span open at the time."""
    pd = trace_mod.load(trace_dir)
    if keep:
        shutil.copytree(trace_dir, keep, dirs_exist_ok=True)
    shutil.rmtree(trace_dir, ignore_errors=True)
    ctx.trace = red = trace_mod.reduce(trace_mod.extract(pd))
    del pd
    ctx.epochs, prefills, ctx.queue_waits = tracer_facts(
        tracer, ctx.t_open, ctx.t_close, ctx.snaps)
    ctx.prefills = [(n, bucket_for(n)) for n in prefills]
    kernels = []
    for m in cell_metrics(bench, cell, "per_layer"):
        mod = load_metric(root, m["name"])
        v = mod.read(ctx) if mod is not None else None
        if v is not None:
            out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        if hasattr(mod, "LABEL"):
            kernels.append(mod)
    if red is None:
        return

    def label(text):
        return next((k.LABEL for k in kernels if k.matches(text, ctx.dims)),
                    trace_mod.op_label(text))

    out["device"]["busy_s"] = red["busy_s"]
    out["device"]["window_s"] = red["window_s"]
    ops = sorted(trace_mod.by_label(red["ops"], label).items(),
                 key=lambda kv: -kv[1])
    spans = trace_mod.engine_spans(tracer.events, tracer.to_us(ctx.t_open))
    labelled = trace_mod.label_gaps(red["gaps"], spans)
    idle_by = {}
    for name, s in labelled:
        idle_by[name] = idle_by.get(name, 0.0) + s
    out["breakdown"] = {
        "device_ops": [[k, v] for k, v in ops[:10]],
        "idle_gaps": [[k, v] for k, v in sorted(
            labelled, key=lambda kv: -kv[1])[:10]]}
    log(f"trace: busy {red['busy_s']!r} s of {red['window_s']!r} s; "
        f"programs {json.dumps(red['modules'])}; runs "
        f"{json.dumps(red['module_runs'])}")
    log(f"trace: device seconds by op {json.dumps(ops[:40])}")
    log(f"trace: idle by host span {json.dumps(idle_by)}; "
        f"{len(ctx.epochs)} epochs, {len(ctx.prefills)} prefills, "
        f"{len(ctx.queue_waits)} admissions in the window")


def main(argv=None, require_chip: bool = True,
         root: pathlib.Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the profiler trace to this directory")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="compare the lower-precision control in the "
                    "program's place (its correct has to be false)")
    args = ap.parse_args(argv)

    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = spec.load_cell(root, args.workload)
    family = cell.family
    dims = family.dims_of(cell.config)

    import jax
    import numpy as np

    devs = jax.devices()
    kind = devs[0].device_kind
    if require_chip and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        print(f"chipbench: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devs)} {devs[0].platform} device(s) ({kind})",
              file=sys.stderr)
        return 2
    pk = peaks.peaks(kind) if require_chip else peaks.PEAKS["TPU v5 lite"]
    cache_dir = enable_compile_cache() if require_chip else None
    meter = CompileMeter()
    log(f"device: {devs[0].platform} {kind} x{len(devs)}; cell "
        f"{cell.name}; seed {args.seed}; compile cache {cache_dir}")

    tracer = None
    if args.trace:
        from repro.obs.trace import Tracer
        tracer = Tracer()
    t = perf_counter()
    cfg, eng = drive.build(cell, args.seed, tracer)
    init_s = perf_counter() - t
    pbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(eng.params))
    log(f"config {cell.config_name}: {cfg.num_layers} layers, d_model "
        f"{cfg.d_model}, {cfg.num_heads} heads ({cfg.num_kv_heads} kv) x "
        f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}; "
        f"parameters {pbytes} bytes on the device, built in {init_s!r} s")
    mix, vocab = cell.mix, cfg.vocab_size
    ladder = drive.warm_ladder(eng, mix, vocab, args.seed)
    first = loadgen.residual_budgets(mix)
    log(f"engine: {eng.max_slots} slots, max_len {eng.max_len}, "
        f"{eng.num_pages} pages x {eng.page_size} entries, decode epochs "
        f"of {eng.decode_steps}; warm-up prompts "
        f"{[len(p) for p, _ in ladder]}; first budgets {first}")

    pool = loadgen.RequestPool(mix, args.seed, vocab)
    loop = drive.ClosedLoop(eng, mix["clients"])
    loop.start(pool, ladder, first)
    loop.run_while(lambda: not loop.traffic_started())
    comp_s, comp_n, comp_hits = meter.take()
    setup_s = perf_counter() - T_START
    log(f"set-up {setup_s!r} s: compile {comp_s!r} s over {comp_n} "
        f"programs ({comp_hits} from the persistent cache); warm-up "
        f"{len(loop.reqs)} requests")

    # -- the window ------------------------------------------------------
    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(trace_dir)
    c0 = {k: eng.metrics.value(k) for k in TRACE_COUNTERS}
    t_open = perf_counter()
    t_close = t_open + args.seconds
    with jax.profiler.TraceAnnotation(trace_mod.WINDOW):
        loop.run_until(t_close)
    if args.trace:
        jax.profiler.stop_trace()
    loop.submitting = False
    counters = {k: eng.metrics.value(k) - c0[k] for k in TRACE_COUNTERS}
    win_comp = meter.take()
    stats = drive.window_stats(loop, t_open, t_close)
    mem = devs[0].memory_stats() or {}
    peak_bytes = int(mem.get("peak_bytes_in_use", 0))
    log(f"window {args.seconds!r} s: {stats['output_tokens']} tokens "
        f"handed out over {stats['handout_span_s']!r} s from the first "
        f"hand-out to the last, {stats['finished']} requests finished, "
        f"{stats['attempted']} submitted (mean prompt "
        f"{stats['mean_prompt']!r}, mean budget {stats['mean_output']!r}; "
        f"the mix's means {loadgen.mean_lengths(mix)}), "
        f"{stats['failed']} failed; {stats['itl_count']} token gaps, "
        f"{stats['ttft_count']} TTFTs (p50 {stats['ttft_p50_ms']!r} ms); "
        f"compiles in the window {win_comp[1]} ({win_comp[0]!r} s); "
        f"preemptions {counters['preemptions_total']!r}, epoch shrinks "
        f"{counters['epoch_shrinks_total']!r}")
    log(f"device memory: peak_bytes_in_use {peak_bytes} of bytes_limit "
        f"{mem.get('bytes_limit')}")

    # -- correctness: free the program, run the reference -----------------
    picked = check.sample(loop.reqs.values(), args.seed, t_open, t_close)
    snaps = [s for s in loop.snaps if t_open <= s.t0 <= t_close]
    reqs = list(loop.reqs.values())
    bucket_for = eng.scheduler.bucket_for
    del eng
    loop.eng = None
    for r in reqs:
        r.handle = None     # a handle holds the engine
    gc.collect()
    t = perf_counter()
    keep, facts = None, {}
    limits = cell.config["correct_limit"]
    compared = check.compared(None, limits)
    if picked:
        seqs, rows = check.sequences(picked)
        modes = (False, True) if args.control else (False,)
        out, facts = family.reference_run(
            cell.config, spec.weights_key(args.seed), seqs, rows,
            lowp_modes=modes)
        res = out[False]
        g = check.served_gaps(res, picked)
        keep = (res.attn_keep, res.mlp_keep)
        stored, ref_stored = check.entry_counts(res, picked)
        log(f"reference over {len(picked)} requests, {len(g)} served "
            f"tokens, in {perf_counter() - t!r} s: served-token logit gap "
            f"max {float(g.max())!r}, p90 {float(np.percentile(g, 90))!r}, "
            f"median {float(np.median(g))!r}; {int((g == 0).sum())} of "
            f"{len(g)} are the reference's greedy token; keep shares "
            f"attention {res.attn_keep!r}, MLP {res.mlp_keep!r}; gates "
            f"against their router's lean {res.against!r}")
        log(f"KV entries stored per request: engine {stored}, reference "
            f"{ref_stored}; attention gates turned by rounding, at least "
            f"{sum(abs(a - b) for a, b in zip(stored, ref_stored))} of "
            f"{sum(len(s) for s in seqs) * (dims.layers - 1)}")
        if args.control:
            g = check.control_gaps(res, out[True])
            log(f"control in the program's place: gap max "
                f"{float(g.max())!r}, median {float(np.median(g))!r}")
        compared = check.compared(g, limits)
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in compared.values())

    # -- metrics ------------------------------------------------------------
    device = {"platform": devs[0].platform, "kind": kind,
              "count": cell.chips, "memory_peak_bytes": peak_bytes}
    out = {"correct": bool(correct), "attempted": stats["attempted"],
           "failed": stats["failed"], "metrics": {}, "device": device}
    if not args.trace:
        values = dict(stats, setup_s=setup_s)
        for m in cell_metrics(bench, cell.name, "end_to_end"):
            v = values.get(m["name"])
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        ctx = types.SimpleNamespace(
            family=family, dims=dims, facts=facts, peaks=pk,
            slots=mix["clients"], trace=None, epochs=[], prefills=[],
            snaps=snaps, queue_waits=[], counters=counters, reqs=reqs,
            t_open=t_open, t_close=t_close, keep=keep,
            lean=tuple(np.asarray(m) for m in family.keep_masks(
                spec.weights_key(args.seed), dims.layers)))
        traced_window(ctx, out, root, bench, cell.name, trace_dir,
                      args.keep_trace, tracer, bucket_for)
    out["compared"] = compared
    for name, c in compared.items():
        print(f"compared: {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
