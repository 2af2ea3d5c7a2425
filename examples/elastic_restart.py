"""Fault-tolerance walkthrough: train on a 4×2 mesh, simulate preemption,
resume from the atomic checkpoint on a SHRUNK 2×2 mesh (elastic scaling via
reshard-on-restore).  Runs on 8 forced CPU host devices.

  PYTHONPATH=src python examples/elastic_restart.py
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import dataclasses
import tempfile

import jax
import numpy as np

from repro.configs import get_config
from repro.distributed.sharding import ShardingPolicy
from repro.launch.mesh import make_mesh
from repro.train import checkpoint as ck
from repro.train.fault_tolerance import ElasticPlan
from repro.train.trainer import Trainer, TrainerConfig


def main():
    cfg = dataclasses.replace(get_config("qwen3-8b").smoke(), num_layers=2)
    ckpt = tempfile.mkdtemp(prefix="elastic_")
    common = dict(seq_len=32, global_batch=8, lr=1e-3, log_every=2,
                  ckpt_every=4, ckpt_dir=ckpt)

    print("phase 1: train on 4x2 mesh (8 'chips'), checkpoint every 4 steps")
    mesh = make_mesh((4, 2), ("data", "model"))
    with mesh:
        tr = Trainer(cfg, TrainerConfig(steps=8, **common),
                     ShardingPolicy(mesh, cfg, mode="train"))
        tr.run()
    step = ck.latest_step(ckpt)
    print(f"  ... 'preempted' after checkpoint at step {step}")

    print("phase 2: one host lost -> ElasticPlan remaps the mesh")
    plan = ElasticPlan(model=2)
    new_mesh_shape = plan.mesh_for(surviving_chips=4)
    print(f"  surviving=4 chips -> mesh {new_mesh_shape}")

    mesh2 = make_mesh(new_mesh_shape, ("data", "model"))
    with mesh2:
        tr2 = Trainer(cfg, TrainerConfig(steps=16, **common),
                      ShardingPolicy(mesh2, cfg, mode="train"))
        state = tr2.run(resume=True)   # reshard-on-restore
    print(f"  resumed from step {step} and finished at step "
          f"{int(state['data_step'])} on the {new_mesh_shape} mesh")
    for m in tr2.metrics_log:
        print(f"  step {m['step']:3d}  loss {m['loss']:.3f}")
    print("elastic restart complete — loss curve continued across meshes")


if __name__ == "__main__":
    main()
