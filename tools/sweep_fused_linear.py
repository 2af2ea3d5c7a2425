#!/usr/bin/env python3
"""Time one fused-linear call per tiling at the benchmark's linear shapes.

    python3 tools/sweep_fused_linear.py [--out results/sweep.jsonl]
    python3 tools/sweep_fused_linear.py --smoke      # CPU rehearsal

For each linear of qwen3-8b and deepseek-coder-33b (the four calls of a
layer, as the model makes them: norm prologue on ``wqkv`` and the
``[gate | up]`` SwiGLU, gate multiplier + residual + Σy² on ``wo`` and
``down``; int4 codes of group 128 stored one per byte) at the decode
rows (M = 16), and for deepseek's ``[gate | up]`` at one prefill length,
it times ``fused_linear_pallas`` under the tiles ``fused_linear_plan``
picks, under one group and 128 columns a step (the tiling before the
plan), and through the plan along each axis: ``bk`` over {128, 512,
1024, 2048} at the planned ``bn``, ``bn`` over {128, 512, 1280, 2048}
at the planned ``bk`` (prefill: ``bm`` over {128, 256, 512}), each cut
to the largest tile that divides the axis.  ``--plan-only`` times the
first two alone.  A call runs ``--reps`` times inside one jitted loop
whose input is x plus a scalar of the previous output, so no call can
be hoisted or merged and host dispatch is not timed.

One JSON line per (shape, tiles): the call's microseconds, its grid
steps, microseconds per step, and the stored weight bytes (codes at one
byte, scales at four) per second.  A table goes to standard output.
Without ``--smoke`` it refuses to run unless JAX's first device is a TPU.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels.fused_linear import (fused_linear_pallas,  # noqa: E402
                                        fused_linear_plan)

GROUP = 128
# (config, linear, K, N, kind): kind "norm" = prologue, "glu" = prologue
# + SwiGLU, "res" = gate multiplier + residual + Σy²
LINEARS = [
    ("qwen3-8b", "wqkv", 4096, 6144, "norm"),
    ("qwen3-8b", "wo", 4096, 4096, "res"),
    ("qwen3-8b", "gu", 4096, 2 * 12288, "glu"),
    ("qwen3-8b", "down", 12288, 4096, "res"),
    ("deepseek-coder-33b", "wqkv", 7168, 9216, "norm"),
    ("deepseek-coder-33b", "wo", 7168, 7168, "res"),
    ("deepseek-coder-33b", "gu", 7168, 2 * 19200, "glu"),
    ("deepseek-coder-33b", "down", 19200, 7168, "res"),
]
BK_TARGETS = (128, 512, 1024, 2048)
BN_TARGETS = (128, 512, 1280, 2048)
BM_TARGETS = (128, 256, 512)
PREFILL = ("deepseek-coder-33b", "gu", 1024)


def divisor_at_most(total: int, target: int, unit: int) -> int:
    """The largest multiple of ``unit`` that divides ``total`` and is at
    most ``target`` (``unit`` when none is)."""
    best = unit
    for t in range(unit, min(total, target) + 1, unit):
        if total % t == 0:
            best = t
    return best


def make_inputs(key, M: int, K: int, N: int, kind: str):
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (M, K), jnp.float32).astype(jnp.bfloat16)
    codes = jax.random.randint(ks[1], (K, N), -8, 8, jnp.int8)
    scale = jnp.exp2(jax.random.randint(ks[2], (K // GROUP, N), -9, -5)
                     .astype(jnp.float32))
    F = N // 2 if kind == "glu" else N
    extra = {}
    if kind in ("norm", "glu"):
        extra["mean_sq"] = jnp.ones((M,), jnp.float32)
        extra["gamma"] = jnp.ones((K,), jnp.bfloat16)
    if kind == "glu":
        extra.update(glu=True, act="silu")
    if kind == "res":
        extra["residual"] = jax.random.normal(
            ks[3], (M, F), jnp.float32).astype(jnp.bfloat16)
        extra["gate_mul"] = jnp.ones((M,), jnp.float32)
        extra["emit_sq"] = True
    return x, codes, scale, extra


def timed_loop(x, codes, scale, extra, tiles, reps: int, interpret: bool):
    """A jitted loop of ``reps`` calls; each call's input adds a scalar of
    the previous call's output."""
    bm, bn, bk = tiles if tiles is not None else (None, None, None)
    arrays = {k: v for k, v in extra.items() if isinstance(v, jax.Array)}
    flags = {k: v for k, v in extra.items() if not isinstance(v, jax.Array)}

    @jax.jit
    def loop(x, codes, scale, arrs):
        def body(_, carry):
            return fused_linear_pallas(
                x + jnp.tanh(carry), w_codes=codes, scale=scale, bm=bm,
                bn=bn, bk=bk, interpret=interpret, **arrs, **flags)[0][0, 0]
        return jax.lax.fori_loop(0, reps, body, jnp.zeros((), x.dtype))

    # the weights are arguments: captured, they would be compiled in as
    # constants
    return lambda: loop(x, codes, scale, arrays)


def measure(run, reps: int, tries: int = 2):
    t0 = time.perf_counter()
    jax.block_until_ready(run())                 # compile and warm
    first = time.perf_counter() - t0
    best = float("inf")
    for _ in range(tries):
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        best = min(best, time.perf_counter() - t0)
    return best / reps, first


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=str(ROOT / "results" /
                                         "sweep_fused_linear.jsonl"))
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes in interpret mode (CPU rehearsal)")
    ap.add_argument("--configs", default="qwen3-8b,deepseek-coder-33b",
                    help="comma-separated configurations to time")
    ap.add_argument("--plan-only", action="store_true",
                    help="time the planned and the one-group tiles only")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if not args.smoke and dev.platform != "tpu":
        print(f"sweep: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    # the rehearsal cuts qwen's widths by 8 (deepseek's do not divide)
    div = 8 if args.smoke else 1
    prefill = ("qwen3-8b",) + PREFILL[1:] if args.smoke else PREFILL
    wanted = set(args.configs.split(","))
    linears = [(c, n, K // div, N // div, kind)
               for c, n, K, N, kind in LINEARS
               if c in wanted and (not args.smoke or c == "qwen3-8b")]
    jobs = []
    for cfg, name, K, N, kind in linears:
        F = N // 2 if kind == "glu" else N
        rows = [16]
        if (cfg, name) == prefill[:2]:
            rows.append(prefill[2] // div)
        for m in rows:
            p = fused_linear_plan(m, K, F, GROUP, kind == "glu", True)
            tiles = {(min(128, m), 128, GROUP)}
            if not args.plan_only:
                tiles |= {(p.bm, p.bn, divisor_at_most(K, bk, GROUP))
                          for bk in BK_TARGETS}
                tiles |= {(p.bm, divisor_at_most(F, bn, 128), p.bk)
                          for bn in BN_TARGETS}
                if m > 16:
                    tiles |= {(min(bm, m), p.bn, p.bk) for bm in BM_TARGETS}
            tiles.discard(tuple(p[:3]))
            jobs.append((cfg, name, m, K, N, kind, sorted(tiles)))

    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    print(f"sweep: {dev.platform} {dev.device_kind}; {args.reps} calls per "
          f"timing, best of 2", flush=True)
    key = jax.random.PRNGKey(args.seed)
    with out.open("w") as fh:
        for cfg, name, M, K, N, kind, tiles in jobs:
            F = N // 2 if kind == "glu" else N
            x, codes, scale, extra = make_inputs(key, M, K, N, kind)
            plan = fused_linear_plan(M, K, F, GROUP, kind == "glu", True)
            wbytes = codes.size + scale.size * 4
            for t in tiles + [None]:
                bm, bn, bk = t if t is not None else plan[:3]
                steps = -(-M // bm) * -(-F // bn) * -(-K // bk)
                run = timed_loop(x, codes, scale, extra, t, args.reps,
                                 args.smoke)
                sec, first = measure(run, args.reps)
                row = {"config": cfg, "linear": name, "M": M, "K": K,
                       "N": N, "bm": bm, "bn": bn, "bk": bk,
                       "planned": t is None, "steps": steps,
                       "call_us": sec * 1e6, "us_per_step": sec * 1e6 / steps,
                       "weight_GBps": wbytes / sec / 1e9,
                       "compile_and_first_s": first}
                fh.write(json.dumps(row) + "\n")
                fh.flush()
                print(f"{cfg:20s} {name:5s} M={M:5d} bm={bm:4d} bn={bn:5d} "
                      f"bk={bk:5d} {'plan' if t is None else '    '} "
                      f"steps={steps:6d} call={sec * 1e6:10.1f}us "
                      f"step={sec * 1e6 / steps:7.3f}us "
                      f"{wbytes / sec / 1e9:7.1f}GB/s", flush=True)
            del x, codes, scale, extra
    return 0


if __name__ == "__main__":
    sys.exit(main())
