"""Benchmark harness — one module per paper table/figure.

  python -m benchmarks.run [--quick] [--only fig8,...] [--out-dir DIR]

Prints ``name,us_per_call,derived`` CSV rows and writes one
machine-readable ``BENCH_<name>.json`` per suite entry (per-bench
wall-clock + any roofline byte accounting the bench attaches via
``Rows.meta``) — the CI perf artifact, so the perf trajectory is
recorded run over run.
"""
import argparse
import json
import pathlib
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--out-dir", default=".",
                    help="directory for BENCH_<name>.json artifacts")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (bench_bandwidth, bench_chunked_prefill,
                            bench_end_to_end, bench_fault_tolerance,
                            bench_fused_linear, bench_kv_storage,
                            bench_mha_dataflow, bench_observability,
                            bench_paged_kv, bench_pe_accuracy,
                            bench_roofline, bench_serve,
                            bench_speculative)
    suite = {
        "table1_pe_accuracy": bench_pe_accuracy,
        "fig8_mha_dataflow": bench_mha_dataflow,
        "fig9_bandwidth": bench_bandwidth,
        "kv_storage_25pct": bench_kv_storage,
        "table3_end_to_end": bench_end_to_end,
        "serve_continuous": bench_serve,
        "paged_kv": bench_paged_kv,
        "fused_linear": bench_fused_linear,
        "chunked_prefill": bench_chunked_prefill,
        "observability": bench_observability,
        "fault_tolerance": bench_fault_tolerance,
        "speculative": bench_speculative,
        "roofline": bench_roofline,
    }
    only = set(args.only.split(",")) if args.only else None
    out_dir = pathlib.Path(args.out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    failed = 0
    print("name,us_per_call,derived")
    for name, mod in suite.items():
        if only and name not in only:
            continue
        try:
            rows = mod.run(quick=args.quick)
            rows.emit()
            (out_dir / f"BENCH_{name}.json").write_text(
                json.dumps(rows.to_json(name), indent=1, sort_keys=True))
        except Exception:
            failed += 1
            print(f"{name},0.0,ERROR", file=sys.stdout)
            traceback.print_exc()
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
