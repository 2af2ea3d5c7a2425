import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture × input shape) against the production mesh and record
memory_analysis / cost_analysis / collective schedule for §Dry-run and
§Roofline.

The two lines above run before ANY other import — jax locks the device
count at first init.  This module is the ONLY place that requests 512
placeholder devices.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only-cell ...]
Results cached as JSON under results/dryrun/.
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

import jax

from repro.configs import ASSIGNED_ARCHS, SHAPES, get_config
from repro.distributed.sharding import ShardingPolicy
from repro.launch import specs as specs_lib
from repro.launch.mesh import make_production_mesh
from repro.roofline.analysis import (DRYRUN_DEVICE_KIND, analyze_compiled,
                                     model_flops_for)

RESULTS_DIR = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def cell_id(arch: str, shape: str, multi_pod: bool, variant: str = "") -> str:
    pod = "pod2" if multi_pod else "pod1"
    v = f"-{variant}" if variant else ""
    return f"{arch}__{shape}__{pod}{v}"


def run_cell(arch: str, shape: str, multi_pod: bool,
             overrides: dict | None = None, variant: str = "",
             zero1: bool = False, microbatches: int | None = None,
             no_sp: bool = False) -> dict:
    cfg = get_config(arch)
    if overrides:
        quant_over = {k[6:]: v for k, v in overrides.items()
                      if k.startswith("quant_")}
        plain = {k: v for k, v in overrides.items()
                 if not k.startswith("quant_")}
        if quant_over:
            plain["quant"] = dataclasses.replace(cfg.quant, **quant_over)
        cfg = dataclasses.replace(cfg, **plain)
    rec: dict = {"arch": arch, "shape": shape,
                 "mesh": "2x16x16" if multi_pod else "16x16",
                 "variant": variant or "baseline"}
    if shape not in cfg.supported_shapes():
        rec["status"] = "skipped"
        rec["reason"] = ("long-context decode requires sub-quadratic "
                        "attention (DESIGN.md §Arch-applicability)")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    kind = SHAPES[shape]["kind"]
    mode = "train" if kind == "train" else "serve"
    policy = ShardingPolicy(mesh, cfg, mode=mode, zero1=zero1)
    if no_sp:
        from jax.sharding import PartitionSpec as P
        policy.overrides["residual"] = P(policy.dp, None, None)
        policy.overrides["kv_view"] = P(policy.dp, None, None, None)
    if kind == "train":
        fn, args, in_sh, out_sh, donate = specs_lib.build_train_step(
            cfg, policy, shape, microbatches=microbatches)
    else:
        fn, args, in_sh, out_sh, donate = specs_lib.build_step(
            cfg, policy, shape)

    t0 = time.time()
    with mesh:
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    tokens = SHAPES[shape]["global_batch"] * (
        SHAPES[shape]["seq_len"] if kind != "decode" else 1)
    chips = mesh.devices.size
    analysis = analyze_compiled(compiled, chips=chips,
                                model_flops=model_flops_for(cfg, kind, tokens),
                                shape_kind=kind,
                                device_kind=DRYRUN_DEVICE_KIND)
    rec.update(status="ok", lower_s=round(t_lower, 1),
               compile_s=round(t_compile, 1), kind=kind,
               tokens=tokens, **analysis)
    return rec


def save(rec: dict, multi_pod: bool) -> Path:
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    p = RESULTS_DIR / (cell_id(rec["arch"], rec["shape"], multi_pod,
                               rec.get("variant", "")
                               if rec.get("variant") != "baseline" else "")
                       + ".json")
    p.write_text(json.dumps(rec, indent=1, default=float))
    return p


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="every (arch x shape) on the single-pod mesh")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--variant", default="",
                    help="tag for optimization variants (hillclimbs)")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg override key=value (python literal); "
                         "quant_* keys override QuantConfig fields")
    ap.add_argument("--zero1", action="store_true",
                    help="ZeRO-1 param sharding (weight-stationary train)")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--no-sp", action="store_true",
                    help="disable sequence-parallel residual carry")
    args = ap.parse_args()

    overrides = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        import ast
        try:
            overrides[k] = ast.literal_eval(v)
        except (ValueError, SyntaxError):
            overrides[k] = v

    cells = []
    if args.all:
        for arch in ASSIGNED_ARCHS:
            for shape in SHAPES:
                cells.append((arch, shape, args.multi_pod))
    else:
        assert args.arch and args.shape
        cells.append((args.arch, args.shape, args.multi_pod))

    failures = 0
    for arch, shape, mp in cells:
        out = RESULTS_DIR / (cell_id(arch, shape, mp, args.variant) + ".json")
        if args.skip_existing and out.exists():
            prev = json.loads(out.read_text())
            if prev.get("status") in ("ok", "skipped"):
                print(f"[cached ] {out.name}")
                continue
        try:
            rec = run_cell(arch, shape, mp, overrides or None, args.variant,
                           zero1=args.zero1, microbatches=args.microbatches,
                           no_sp=args.no_sp)
        except Exception as e:
            traceback.print_exc()
            rec = {"arch": arch, "shape": shape,
                   "mesh": "2x16x16" if mp else "16x16",
                   "variant": args.variant or "baseline",
                   "status": "error", "error": f"{type(e).__name__}: {e}"}
            failures += 1
        p = save(rec, mp)
        if rec["status"] == "ok":
            print(f"[ok {rec['compile_s']:7.1f}s] {p.name}  "
                  f"bottleneck={rec['bottleneck']}  "
                  f"flops/dev={rec['hlo_flops_per_dev']:.3e}  "
                  f"bytes/dev={rec['hlo_bytes_per_dev']:.3e}  "
                  f"coll/dev={rec['collective_bytes_per_dev']:.3e}")
            ma = rec.get("memory_analysis") or {}
            if ma:
                print("           memory_analysis:", {
                    k: f"{v/1e9:.2f}GB" for k, v in ma.items()
                    if "size" in k})
        else:
            print(f"[{rec['status']:7s}] {p.name}  {rec.get('reason', rec.get('error', ''))[:120]}")
        sys.stdout.flush()
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
