"""Training launcher.

Single-process usage (CPU smoke / examples):
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --smoke \
      --steps 50 --batch 8 --seq 128

Cluster usage mirrors the dry-run configuration: the same ShardingPolicy /
mesh / step builder lower the identical program on real TPU pods (the
launcher also sets the XLA latency-hiding-scheduler flags that enable
compute/communication overlap on device).
"""
import os

TPU_PERF_FLAGS = (
    " --xla_tpu_enable_latency_hiding_scheduler=true"
    " --xla_tpu_enable_async_collective_fusion=true"
    " --xla_tpu_overlap_compute_collective_tc=true"
)
if os.environ.get("REPRO_TPU"):
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + TPU_PERF_FLAGS

import argparse
import dataclasses
import json

import jax


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--mesh", default=None,
                    help="data,model e.g. 2,2 (needs that many devices)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.configs import get_config
    from repro.distributed.sharding import ShardingPolicy
    from repro.launch.mesh import make_mesh
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    policy = None
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split(","))
        mesh = make_mesh((d, m), ("data", "model"))
        policy = ShardingPolicy(mesh, cfg, mode="train")

    tcfg = TrainerConfig(seq_len=args.seq, global_batch=args.batch,
                         steps=args.steps, lr=args.lr,
                         ckpt_dir=args.ckpt_dir,
                         grad_compression=args.grad_compression)
    trainer = Trainer(cfg, tcfg, policy)
    state = trainer.run(resume=args.resume)
    for m in trainer.metrics_log:
        print(json.dumps(m))
    print(f"final loss: {trainer.metrics_log[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
