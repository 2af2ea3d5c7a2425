"""Serving launcher: batched generation with the SkipOPU inference
pipeline (gather-mode routing + cross-layer KV reuse).

  PYTHONPATH=src python -m repro.launch.serve --arch llama2-7b --smoke \
      --batch 4 --prompt-len 64 --new-tokens 32

``build_parser`` / ``build_model`` / ``build_engine`` are the launcher's
construction path; ``chip_smoke.py`` drives the same functions.
"""
import argparse
import dataclasses

import jax
import numpy as np


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights (there are no weight "
                         "files)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--gather", action="store_true",
                    help="compacted (gather) prefill execution")
    ap.add_argument("--int4", action="store_true",
                    help="quantize weights to int4 (paper §4.2)")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching over a --batch-slot KV pool "
                         "(mixed prompt lengths; see docs/serving.md)")
    ap.add_argument("--paged-kv", action="store_true",
                    help="paged KV store + history buffer instead of the "
                         "dense slot pool (see docs/kvcache.md)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=None,
                    help="paged-KV page budget (default: every slot's "
                         "worst case; requires --paged-kv)")
    ap.add_argument("--kv-dtype", default=None,
                    choices=("int8", "int4"),
                    help="quantize paged-KV page payloads (per-entry "
                         "pow2 scales; requires --paged-kv; see "
                         "docs/kvcache.md)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="refcounted prompt-prefix sharing with "
                         "copy-on-write pages: warm admissions skip the "
                         "shared prefill (requires --paged-kv; see "
                         "docs/kvcache.md)")
    ap.add_argument("--prefix-block", type=int, default=16,
                    help="prefix-cache publish granularity in tokens")
    ap.add_argument("--decode-steps", type=int, default=0,
                    help="fuse this many decode iterations into one "
                         "device-resident dispatch (0 = config default; "
                         "1 = per-token parity; requires --continuous; "
                         "see docs/serving.md)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="self-speculative decoding: draft this many "
                         "tokens per window with the layer-skip draft "
                         "pass, verify them in one chunked dispatch "
                         "(0 = off; requires --continuous, incompatible "
                         "with --decode-steps; see docs/speculative.md)")
    ap.add_argument("--draft-keep", type=float, default=None,
                    help="draft-pass router keep-rate lever in (0, 1]: "
                         "lower = cheaper, more aggressively skipped "
                         "drafts at lower acceptance (default: serve "
                         "keep rate; requires --spec-k)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: process prompts this many "
                         "tokens at a time, interleaved with resident "
                         "decode steps (0 = monolithic; requires "
                         "--continuous; see docs/serving.md)")
    ap.add_argument("--use-kernels", action="store_true",
                    help="Pallas kernel path incl. the fused linear "
                         "pipeline (interpret mode off-TPU — slow on "
                         "CPU, for end-to-end validation)")
    ap.add_argument("--tp", type=int, default=0,
                    help="tensor-parallel degree: serve over a (1, N) "
                         "device mesh with head-sharded attention/KV and "
                         "column/row-split linears (requires --continuous; "
                         "token output is identical to --tp 0 — see "
                         "docs/distributed.md)")
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="write a Chrome trace-event JSON of the run "
                         "(open in Perfetto / chrome://tracing; requires "
                         "--continuous; see docs/observability.md)")
    ap.add_argument("--metrics-out", default=None, metavar="FILE",
                    help="write the run's metrics snapshot (.prom suffix "
                         "= Prometheus text format, else JSON; requires "
                         "--continuous)")
    # robustness / lifecycle flags (docs/robustness.md; all require
    # --continuous)
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request wall-clock budget from submission; "
                         "past it a request finishes with reason "
                         "'deadline' and releases its slot/pages at the "
                         "next step boundary")
    ap.add_argument("--snapshot-dir", default=None, metavar="DIR",
                    help="write crash-consistent engine snapshots at "
                         "quiescent step boundaries (resume with --resume)")
    ap.add_argument("--snapshot-every", type=int, default=1,
                    help="boundaries between snapshots (default 1)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the newest snapshot under "
                         "--snapshot-dir instead of submitting fresh "
                         "requests; at temperature 0 the survivors' "
                         "tokens are bit-identical to the uninterrupted "
                         "run")
    ap.add_argument("--kill-at", type=int, default=None, metavar="N",
                    help="inject a SimulatedKill at step boundary N "
                         "(after its snapshot) — exits with code 3; used "
                         "by tools/kill_resume_smoke.py")
    ap.add_argument("--watchdog-timeout-s", type=float, default=None,
                    help="hard bound on one dispatch+sync; past it the "
                         "run aborts with HungDispatch (trace attached)")
    ap.add_argument("--max-queue-depth", type=int, default=None,
                    help="shed new submissions once the queue is this "
                         "deep")
    ap.add_argument("--max-queue-delay-s", type=float, default=None,
                    help="shed new submissions once the queue head has "
                         "waited past this bound")
    ap.add_argument("--max-preemptions", type=int, default=None,
                    help="per-request eviction retry budget; past it a "
                         "victim keeps its partial tokens (reason "
                         "'preempt_budget') instead of requeueing")
    ap.add_argument("--results-out", default=None, metavar="FILE",
                    help="write per-request results (tokens, finish "
                         "reason) as JSON — the kill/resume smoke "
                         "compares these across runs")
    return ap


def check_args(args: argparse.Namespace) -> None:
    """Reject flag combinations the engine cannot serve (SystemExit)."""
    if args.prefill_chunk and not args.continuous:
        raise SystemExit("--prefill-chunk requires --continuous")
    if args.decode_steps and not args.continuous:
        raise SystemExit("--decode-steps requires --continuous")
    if args.spec_k and not args.continuous:
        raise SystemExit("--spec-k requires --continuous")
    if args.spec_k and args.decode_steps:
        raise SystemExit("--spec-k and --decode-steps are mutually "
                         "exclusive (both own the decode cadence)")
    if ((args.kv_dtype or args.prefix_cache or args.num_pages)
            and not args.paged_kv):
        raise SystemExit("--kv-dtype/--prefix-cache/--num-pages require "
                         "--paged-kv")
    if args.draft_keep is not None and not args.spec_k:
        raise SystemExit("--draft-keep requires --spec-k")
    if args.tp and not args.continuous:
        raise SystemExit("--tp requires --continuous")
    if (args.trace_out or args.metrics_out) and not args.continuous:
        raise SystemExit("--trace-out/--metrics-out require --continuous")
    robust = (args.deadline_s, args.snapshot_dir, args.kill_at,
              args.watchdog_timeout_s, args.max_queue_depth,
              args.max_queue_delay_s, args.max_preemptions,
              args.results_out, args.resume or None)
    if any(v is not None for v in robust) and not args.continuous:
        raise SystemExit("robustness flags (--deadline-s/--snapshot-dir/"
                         "--resume/--kill-at/...) require --continuous")


def model_config(args: argparse.Namespace):
    """The arch's ModelConfig with the launcher's levers applied."""
    from repro.configs import get_config

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    if args.use_kernels:
        cfg = dataclasses.replace(cfg, use_kernels=True)
    if args.gather:
        cfg = dataclasses.replace(
            cfg, skip=dataclasses.replace(cfg.skip, mode="gather"))
    return cfg


def build_model(args: argparse.Namespace):
    """(cfg, params): ``model_config`` and seeded random weights (there
    are no weight files), int4-coded layer by layer with --int4."""
    from repro.models import model as model_lib

    cfg = model_config(args)
    params = model_lib.init_params(jax.random.PRNGKey(args.seed), cfg,
                                   quantize=args.int4)
    return cfg, params


def build_engine(args: argparse.Namespace, cfg, params, mesh=None):
    """The continuous-batching engine the flags describe (``mesh``: the
    ``--tp`` serving mesh, or None for one device)."""
    from repro.serve.config import (EngineConfig, KVConfig, ObsConfig,
                                    RobustnessConfig, SchedulingConfig,
                                    SpecConfig)
    from repro.serve.engine import ContinuousBatchingEngine
    from repro.serve.faults import Fault, Watchdog

    faults = ([Fault("kill", step=args.kill_at)]
              if args.kill_at is not None else None)
    watchdog = (Watchdog(timeout_s=args.watchdog_timeout_s)
                if args.watchdog_timeout_s is not None else None)
    return ContinuousBatchingEngine(cfg, params, config=EngineConfig(
        kv=KVConfig(
            kv_mode="paged" if args.paged_kv else "dense",
            page_size=args.page_size,
            num_pages=args.num_pages,
            kv_dtype=args.kv_dtype,
            prefix_cache=args.prefix_cache,
            prefix_block=args.prefix_block),
        scheduling=SchedulingConfig(
            max_slots=args.batch,
            max_len=args.prompt_len + args.new_tokens,
            prefill_chunk=args.prefill_chunk,
            decode_steps=args.decode_steps or None),
        spec=SpecConfig(spec_k=args.spec_k, draft_keep=args.draft_keep),
        robustness=RobustnessConfig(
            faults=faults, watchdog=watchdog,
            snapshot_dir=args.snapshot_dir,
            snapshot_every=args.snapshot_every,
            max_queue_depth=args.max_queue_depth,
            max_queue_delay_s=args.max_queue_delay_s,
            max_preemptions=args.max_preemptions),
        obs=ObsConfig(trace=args.trace_out, mesh=mesh),
        temperature=args.temperature))


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    check_args(args)

    from repro.launch.compile_cache import enable_compile_cache
    from repro.serve.engine import ServeEngine

    enable_compile_cache()
    cfg, params = build_model(args)
    rng = np.random.default_rng(args.seed)
    max_len = args.prompt_len + args.new_tokens
    mesh = None
    if args.tp:
        from repro.launch.mesh import make_serve_mesh
        mesh = make_serve_mesh(args.tp)
        print(f"tensor-parallel serving: mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}")
    if args.continuous:
        from repro.serve.errors import SimulatedKill
        eng = build_engine(args, cfg, params, mesh)
        if args.resume:
            at = eng.resume()
            print(f"resumed from snapshot boundary {at} "
                  f"under {args.snapshot_dir}")
        else:
            # mixed-length synthetic traffic: 2x oversubscribed slots
            for _ in range(2 * args.batch):
                ln = int(rng.integers(max(args.prompt_len // 4, 1),
                                      args.prompt_len + 1))
                eng.submit(rng.integers(0, cfg.vocab_size, (ln,),
                                        dtype=np.int32),
                           max_new_tokens=args.new_tokens,
                           deadline_s=args.deadline_s)
        try:
            out = eng.run()
        except SimulatedKill as e:
            print(f"simulated kill: {e}")
            raise SystemExit(3)
        s = out["stats"]
        print(f"prefill: {s.prefill_tokens} tok in {s.prefill_s:.2f}s | "
              f"decode: {s.decode_tok_per_s:.1f} tok/s | "
              f"requests: {s.requests_completed} | "
              f"KV storage saved≈{s.kv_saved_fraction:.1%} (measured) | "
              f"compiles: {s.compiles}")
        if args.spec_k:
            print(f"speculative: k={args.spec_k} "
                  f"draft_keep={eng.draft_keep:.2f} | "
                  f"{s.spec_windows} windows | acceptance "
                  f"{s.spec_acceptance_rate:.1%} "
                  f"({s.spec_tokens_accepted}/{s.spec_tokens_drafted}) | "
                  f"rolled back {s.spec_entries_rolled_back} entries")
        if eng.decode_steps > 1:
            print(f"fused decode: {eng.decode_steps} steps/dispatch | "
                  f"{s.decode_dispatches} dispatches | host "
                  f"{s.host_s:.2f}s vs device-wait {s.device_s:.2f}s")
        if args.prefill_chunk:
            worst = max(r.max_decode_stall_s for r in out["results"].values())
            print(f"chunked prefill: {s.prefill_chunks} chunks | "
                  f"{s.interleaved_steps} interleaved steps | worst "
                  f"decode stall {worst*1e3:.1f}ms")
        if s.kv_mode == "paged":
            print(f"paged KV: peak {s.pages_peak}/{s.pages_total} pages "
                  f"(×{s.page_size} entries) | live entry "
                  f"saving {s.kv_entries_saved_fraction:.1%} | history "
                  f"hit rate {s.history_hit_rate:.1%} | "
                  f"preemptions {s.preemptions}")
        if args.kv_dtype:
            print(f"quantized KV: {args.kv_dtype} page payloads "
                  "(pow2 per-entry scales)")
        if args.prefix_cache:
            print(f"prefix cache: {s.prefix_hits} warm / "
                  f"{s.prefix_misses} cold admissions | "
                  f"{s.prefix_tokens_saved} prefill tokens skipped | "
                  f"{s.prefix_records} records resident")
        if (s.faults_injected or s.requests_cancelled or s.deadline_exceeded
                or s.requests_shed or s.snapshots or s.resumes):
            print(f"robustness: faults {s.faults_injected} | retries "
                  f"{s.dispatch_retries} | deadline {s.deadline_exceeded} "
                  f"| cancelled {s.requests_cancelled} | shed "
                  f"{s.requests_shed} | snapshots {s.snapshots} | "
                  f"resumes {s.resumes}")
        for uid, r in sorted(out["results"].items()):
            print(f"  req {uid}: T0={r.prompt_len} +{r.decode_tokens} "
                  f"TTFT {r.ttft_s*1e3:.1f}ms ({r.finish_reason})")
        if args.results_out:
            import json
            import pathlib
            rpath = pathlib.Path(args.results_out)
            rpath.parent.mkdir(parents=True, exist_ok=True)
            rpath.write_text(json.dumps(
                {str(uid): {"tokens": [int(t) for t in r.tokens],
                            "prompt_len": r.prompt_len,
                            "finish_reason": r.finish_reason}
                 for uid, r in sorted(out["results"].items())}, indent=1))
            print(f"results written to {args.results_out}")
        if args.trace_out:
            print(f"trace written to {args.trace_out} "
                  "(open in https://ui.perfetto.dev)")
        if args.metrics_out:
            import pathlib
            mpath = pathlib.Path(args.metrics_out)
            mpath.parent.mkdir(parents=True, exist_ok=True)
            if mpath.suffix == ".prom":
                mpath.write_text(out["metrics"].to_prometheus())
            else:
                import json
                mpath.write_text(json.dumps(out["metrics"].snapshot(),
                                            indent=2))
            print(f"metrics written to {args.metrics_out}")
        return

    prompts = rng.integers(
        0, cfg.vocab_size, (args.batch, args.prompt_len), dtype=np.int32)
    eng = ServeEngine(cfg, params, max_len=max_len,
                      temperature=args.temperature)
    out = eng.generate(prompts, args.new_tokens)
    s = out["stats"]
    print(f"prefill: {s.prefill_tokens} tok in {s.prefill_s:.2f}s | "
          f"decode: {s.decode_tok_per_s:.1f} tok/s | "
          f"attn keep≈{s.attn_keep_frac:.2f} | "
          f"KV storage saved≈{s.kv_saved_fraction:.1%} (measured; "
          f"analytic≈{s.kv_saved_analytic:.1%})")
    print("sample:", out["tokens"][0, :16])


if __name__ == "__main__":
    main()
