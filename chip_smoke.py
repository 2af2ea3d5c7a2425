#!/usr/bin/env python3
"""Chip smoke: the paged serving engine at llama2-7b's published widths
(32 layers, d_model 4096, 32 heads, d_ff 11008, vocab 32000) on one TPU.

    python3 chip_smoke.py            # one chip: the main serving path
    python3 chip_smoke.py --tp 4     # four chips: TP-4 mesh vs one chip

One process drives every phase.  The weights are random, made from
``--seed``, int4-coded (group 128) layer by layer; the prompts come from
the same seed.  The one-chip run:

1. refuses to start unless JAX's first device is a TPU;
2. builds the model and the engine through the serve launcher's own
   ``build_model`` / ``build_engine`` (paged KV, Pallas kernels, int4
   weights, fused decode epochs);
3. serves ``N_REQUESTS`` prompts of ``MIN_PROMPT``..``MAX_PROMPT`` tokens
   on ``SLOTS`` slots, ``NEW_TOKENS`` greedy tokens each — three times:
   cold, again after dropping the in-memory compiled programs (so the
   persistent compile cache is what serves them) and once fully warm —
   and checks every result;
4. compares the decode path with the kernels against the jnp path
   (``use_kernels=False``) on the same int4 weights: the engine serves one
   prompt ``N_CHECK`` greedy tokens, and both paths take that prompt and
   those tokens through their own prefill and paged decode steps
   (teacher-forced); the next-token logits at every served position are
   held to ``LOGIT_BOUND`` and each served token to the jnp argmax up to
   a near-tie.

``--tp N`` runs only the tensor-parallel check: the same prompts, with
``TP_NEW_TOKENS`` new tokens each, served on ``make_serve_mesh(N)`` and on
one chip (docs/distributed.md).  The two placements' teacher-forced
logits at every served position are held to ``LOGIT_BOUND``, and their
greedy tokens must be identical up to a split at a near-tie.  Facts go to
the earlier lines; the last line is the JSON contract
``{"ok": true, "device": {...}}``.  Any failed check raises, so the exit
code is non-zero and that line is never printed.
"""
import argparse
import dataclasses
import functools
import gc
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent

ARCH = "llama2-7b"
EXTRA_SERVE_FLAGS = []     # appended to the launcher flags below
SLOTS = 4
N_REQUESTS = 8
MIN_PROMPT, MAX_PROMPT = 128, 1024
NEW_TOKENS = 64
TP_NEW_TOKENS = 16         # --tp: the same prompts, fewer tokens (chip time)
DECODE_STEPS = 8           # fused decode epoch length
PAGE_BUDGET_BYTES = 2.5e9  # paged-KV store on the device
# Entries per page: one entry is one (token, executed layer), so 512 is
# 16 tokens of all 32 layers.  The paged-attention kernel walks one page
# of one KV head per grid step; with 16-entry pages a full-width decode
# step took about 2.9 s on a v5e, nearly all of it per-step overhead.
PAGE_SIZE = 512
N_CHECK = 16               # served tokens compared with the jnp path
# Relative L2 distance of the next-token logits at a served position,
# between the kernel and jnp paths and between two placements.  The
# kernels quantize activations to 8-bit block floating point inside the
# int4 matmuls and the jnp path does not (about 4% at d_model 512 over 32
# layers in interpret mode); a placement changes only rounding.  A wrong
# kernel or a misplaced shard moves the logits by far more.
LOGIT_BOUND = 0.1
# The comparisons run with every router keeping every token: a random
# router sits at its decision threshold, where any rounding difference
# flips a gate and moves the logits by far more than the arithmetic does.
KEEP_BIAS = 1e6


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def require_tpu(jax):
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found {len(devs)} "
                         f"{d.platform} device(s) ({d.device_kind})")
    return devs


class CompileMeter:
    """Seconds JAX spends compiling (or loading from the persistent cache)
    and how many programs the persistent cache served."""

    def __init__(self, monitoring):
        self.seconds, self.programs, self.cache_hits = 0.0, 0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

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


def launcher_args(launcher, paged_mod, jax, seed: int, use_kernels: bool,
                  extra=()):
    """The serve launcher's flags for this smoke, parsed and checked by
    the launcher itself; the page budget is PAGE_BUDGET_BYTES' worth of
    pages (payload and entry metadata)."""
    flags = ["--arch", ARCH, "--seed", str(seed), "--int4",
             "--continuous", "--paged-kv", "--batch", str(SLOTS),
             "--prompt-len", str(MAX_PROMPT),
             "--new-tokens", str(NEW_TOKENS),
             "--decode-steps", str(DECODE_STEPS),
             "--page-size", str(PAGE_SIZE)]
    if use_kernels:
        flags.append("--use-kernels")
    flags += list(extra) + EXTRA_SERVE_FLAGS
    args = launcher.build_parser().parse_args(flags)
    cfg = launcher.model_config(args)
    one = jax.eval_shape(
        lambda: paged_mod.init_store(cfg, 1, args.page_size))
    per_page = sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(one))
    args.num_pages = int(PAGE_BUDGET_BYTES // per_page)
    launcher.check_args(args)
    return args


def make_prompts(seed: int, vocab: int):
    rng = np.random.default_rng(seed)
    lens = [MAX_PROMPT] + [int(n) for n in rng.integers(
        MIN_PROMPT, MAX_PROMPT + 1, N_REQUESTS - 1)]
    return [rng.integers(0, vocab, (n,), dtype=np.int32) for n in lens]


def serve(eng, prompts, new_tokens=NEW_TOKENS):
    """Submit every prompt, drain the engine; (results in prompt order,
    stats, wall seconds)."""
    uids = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
    t0 = time.perf_counter()
    out = eng.run()
    wall = time.perf_counter() - t0
    return [out["results"][u] for u in uids], out["stats"], wall


def check_results(results, vocab: int, new_tokens=NEW_TOKENS) -> None:
    for i, r in enumerate(results):
        toks = np.asarray(r.tokens)
        check(r.finish_reason in ("length", "stop"),
              f"request {i} finished with {r.finish_reason!r}")
        check(toks.size > 0 and toks.min() >= 0 and toks.max() < vocab,
              f"request {i}: tokens outside the vocabulary")
        if r.finish_reason == "length":
            check(toks.size == new_tokens,
                  f"request {i}: {toks.size} tokens for budget {new_tokens}")


def tokens_of(results):
    return [np.asarray(r.tokens).tolist() for r in results]


def linear_leaves(params, jax):
    """(int4-coded, still dense) counts of the stack's linear weights large
    enough to quantize (routers and norms stay in floating point)."""
    from repro.quant.int4 import MIN_SIZE
    coded = dense = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            params["stack"])[0]:
        name = getattr(path[-1], "key", "")
        stacked = any(getattr(k, "key", "") == "stages" for k in path)
        per_stage = leaf.size // (leaf.shape[0] if stacked else 1)
        if name == "w_int":
            coded += 1
        elif name == "w" and per_stage >= MIN_SIZE:
            dense += 1
    return coded, dense


def keep_every_token(params, jax, jnp):
    """The same weights with every router biased to keep every token."""
    def one(path, leaf):
        names = [getattr(k, "key", "") for k in path]
        if names[-2:] == ["router", "b"]:
            return jnp.zeros_like(leaf).at[..., 1].set(KEEP_BIAS)
        return leaf

    return jax.tree_util.tree_map_with_path(one, params)


def rel_l2(a, b):
    """Row-wise ||a - b|| / ||b|| over the last axis."""
    return np.linalg.norm(a - b, axis=-1) / np.linalg.norm(b, axis=-1)


def decode_logits(jax, jnp, model, paged_mod, cfg, params, prompt, tokens):
    """Next-token logits [N, V] (float32) at each served position of
    ``prompt`` followed by ``tokens`` (teacher-forced), through the serving
    path's own steps: one prefill, its entries packed into pages of one
    slot, then one paged decode step per token (compiled as the engine
    compiles its programs)."""
    from repro.kvcache import history
    from repro.serve.engine import COMPILER_OPTIONS

    jit = functools.partial(jax.jit, compiler_options=COMPILER_OPTIONS)

    p0, n = len(prompt), len(tokens)
    n_attn = len(cfg.attention_layers)
    cap = (p0 + n) * n_attn
    pages = -(-cap // PAGE_SIZE)
    alloc = paged_mod.PageAllocator(pages, PAGE_SIZE, 1,
                                    slot_entry_capacity=cap)
    reuse = paged_mod.reuse_enabled(cfg)
    lg, cache, st = jit(functools.partial(model.prefill, cfg=cfg))(
        params, {"tokens": jnp.asarray(prompt)[None]})
    gates = np.asarray(st["attn_gate"])[:, 0]
    entries = paged_mod.prefill_entry_count(gates, p0, reuse)
    check(alloc.ensure(0, entries), "decode check: prompt fits its pages")
    store = jit(functools.partial(paged_mod.pack_prefill, cfg=cfg))(
        paged_mod.init_store(cfg, pages, PAGE_SIZE), cache,
        jnp.asarray(gates), jnp.int32(p0),
        jnp.asarray(alloc.block_table[0]))
    alloc.append(0, entries, n_attn * p0)
    del cache
    step = jit(functools.partial(model.paged_decode_step, cfg=cfg))
    rows = [np.asarray(lg[0], np.float32)]
    for j in range(n - 1):
        check(alloc.ensure(0, int(alloc.fill[0]) + n_attn),
              "decode check: step fits its pages")
        lg, store, sp = step(params, store,
                             {"tokens": jnp.asarray([[tokens[j]]], jnp.int32)},
                             jnp.int32(p0 + j),
                             jnp.asarray(alloc.block_table),
                             jnp.asarray(alloc.fill))
        fresh = int(history.fresh_mask(sp["attn_gate"], reuse)[:, 0].sum())
        alloc.append(0, fresh, n_attn)
        rows.append(np.asarray(lg[0], np.float32))
    return np.stack(rows)


def compare_paths(jax, jnp, model, paged_mod, launcher, sargs, cfg, params,
                  prompt):
    """The engine serves ``prompt`` N_CHECK greedy tokens on the kernel
    path (routers keeping every token); the kernel and jnp paths then
    take the prompt and those tokens through their own prefill and paged
    decode steps.  Every served position's logits must agree within
    LOGIT_BOUND, and every served token must be the jnp argmax or within
    a near-tie of it (twice the largest kernel/jnp logit difference at
    that position)."""
    params = keep_every_token(params, jax, jnp)
    eng = launcher.build_engine(sargs, cfg, params)
    res, _, _ = serve(eng, [prompt], N_CHECK)
    check_results(res, cfg.vocab_size, N_CHECK)
    served = np.asarray(res[0].tokens)
    del eng
    gc.collect()
    lk, lj = (decode_logits(
        jax, jnp, model, paged_mod,
        dataclasses.replace(cfg, use_kernels=use_kernels), params, prompt,
        served) for use_kernels in (True, False))
    rel = rel_l2(lk, lj)
    diff = np.abs(lk - lj).max(axis=-1)
    pos = np.arange(N_CHECK)
    deficit = lj.max(axis=-1) - lj[pos, served]
    log(f"logits kernel vs jnp at the first served position (prefill): "
        f"relative L2 {float(rel[0])!r}, max |diff| {float(diff[0])!r} "
        f"(bound: relative L2 <= {LOGIT_BOUND})")
    log(f"logits kernel vs jnp over the {N_CHECK} served positions "
        f"(prefill, then paged decode steps): worst relative L2 "
        f"{float(rel.max())!r}, worst max |diff| {float(diff.max())!r}; "
        f"logit rms {float(np.sqrt((lj ** 2).mean()))!r}")
    log(f"served tokens: {int((served == lj.argmax(-1)).sum())}/{N_CHECK} "
        f"are the jnp argmax, {int((served == lk.argmax(-1)).sum())}/"
        f"{N_CHECK} the kernel step's; worst jnp deficit of a served token "
        f"{float(deficit.max())!r} (near-tie bound 2 x max |diff| there)")
    check(bool((rel <= LOGIT_BOUND).all()),
          f"kernel/jnp logits differ by relative L2 {float(rel.max())} > "
          f"{LOGIT_BOUND}")
    bad = np.flatnonzero(deficit > 2 * diff)
    check(bad.size == 0, f"served tokens at positions {bad.tolist()} are "
          f"not the jnp argmax nor within a near-tie of it")


def one_chip(args, jax, jnp, devs, meter) -> None:
    from repro.kvcache import paged as paged_mod
    from repro.launch import serve as launcher
    from repro.models import model

    sargs = launcher_args(launcher, paged_mod, jax, args.seed, True)

    t = time.perf_counter()
    cfg, params = launcher.build_model(sargs)
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t
    pbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    coded, dense = linear_leaves(params, jax)
    log(f"config {cfg.name}: {cfg.num_layers} layers, d_model "
        f"{cfg.d_model}, {cfg.num_heads} heads ({cfg.num_kv_heads} kv) x "
        f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}; "
        f"int4 group {cfg.quant.group_size}")
    log(f"parameters on the device: {pbytes} bytes; int4-coded linear "
        f"leaves {coded}, dense ones left {dense}; built in {init_s!r} s")
    check(dense == 0, f"{dense} stacked linear weights are not int4-coded")

    eng = launcher.build_engine(sargs, cfg, params)
    check(eng.kv_mode == "paged" and eng.decode_steps == DECODE_STEPS
          and cfg.use_kernels, "engine runs paged KV, kernels, fused epochs")
    log(f"engine: {SLOTS} slots, max_len {eng.max_len}, {eng.num_pages} "
        f"pages x {eng.page_size} entries ({PAGE_BUDGET_BYTES!r} byte "
        f"budget), decode epochs of {eng.decode_steps}")
    prompts = make_prompts(args.seed, cfg.vocab_size)
    log(f"requests: {N_REQUESTS}, prompt lengths "
        f"{[len(p) for p in prompts]}, {NEW_TOKENS} new tokens each, greedy")

    meter.take()
    runs = []
    for label in ("cold", "cache", "warm"):
        if label == "cache":
            jax.clear_caches()      # next compiles come from the disk cache
        res, stats, wall = serve(eng, prompts)
        comp_s, programs, hits = meter.take()
        check(len(res) == N_REQUESTS, "every request has a result")
        check_results(res, cfg.vocab_size)
        runs.append(tokens_of(res))
        log(f"{label} run: {wall!r} s wall, compile {comp_s!r} s over "
            f"{programs} programs ({hits} from the persistent cache), peak "
            f"pages {stats.pages_peak}/{stats.pages_total}, preemptions "
            f"{stats.preemptions}, finish reasons "
            f"{sorted({r.finish_reason for r in res})}")
        if label == "warm":
            # the host's prefill clock also waits out the decode epoch
            # in flight when a prefill is dispatched behind it
            log(f"smoke rates, not benchmark numbers: prefill "
                f"{stats.prefill_tokens / stats.prefill_s!r} tok/s by the "
                f"host clock ({stats.prefill_tokens} tokens), decode "
                f"{stats.decode_tok_per_s!r} tok/s "
                f"({stats.decode_tokens} tokens)")
    check(runs[0] == runs[1] == runs[2],
          "the three runs served different greedy tokens")
    mem = devs[0].memory_stats() or {}
    log(f"device memory: peak_bytes_in_use {mem.get('peak_bytes_in_use')} "
        f"of bytes_limit {mem.get('bytes_limit')}")
    del eng
    gc.collect()

    compare_paths(jax, jnp, model, paged_mod, launcher, sargs, cfg, params,
                  prompts[0])
    comp_s, programs, hits = meter.take()
    log(f"kernel/jnp comparison compiled {programs} programs in "
        f"{comp_s!r} s ({hits} from the persistent cache)")


def forced_logits(jax, jnp, model, cfg, params, prompts, tokens,
                  mesh=None):
    """Next-token logits [R, N, V] (float32) at each served position of
    every prompt followed by its tokens (teacher-forced), from one jnp
    forward over the batch right-padded to one length (causal: the pad
    never reaches a compared position) — on one device, or under the
    serve-mode sharding policy on ``mesh`` as the sharded engine runs;
    compiled as the engine compiles its programs."""
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.distributed.sharding import ShardingPolicy, set_policy
    from repro.serve.engine import COMPILER_OPTIONS

    n = len(tokens[0])
    seq = np.zeros((len(prompts), max(map(len, prompts)) + n), np.int32)
    at = np.zeros((len(prompts), n), np.int32)
    for r, (p, t) in enumerate(zip(prompts, tokens)):
        seq[r, :len(p) + n] = np.concatenate([p, t])
        at[r] = len(p) - 1 + np.arange(n)

    def fn(p, seq, at):
        lg = model.sequence_logits(p, {"tokens": seq}, cfg)
        return jnp.take_along_axis(lg, at[..., None], axis=1).astype(
            jnp.float32)

    if mesh is None:
        return np.asarray(jax.jit(fn, compiler_options=COMPILER_OPTIONS)(
            params, seq, at))
    pol = ShardingPolicy(mesh, cfg, mode="serve")
    psh = pol.param_specs(params)
    rep = NamedSharding(mesh, PartitionSpec())
    with set_policy(pol):
        out = jax.jit(fn, in_shardings=(psh, rep, rep), out_shardings=rep,
                      compiler_options=COMPILER_OPTIONS)(
            jax.device_put(params, psh), seq, at)
    return np.asarray(out)


def tensor_parallel(args, jax, jnp, devs, meter) -> None:
    """The same requests on a TP-N mesh and on one chip, routers keeping
    every token.  Both engines compile to round where the JAX program
    rounds, but which fusions and matmul tilings the compiler picks per
    placement is no contract, so the check allows for rounding: the
    teacher-forced logits must agree within LOGIT_BOUND at every served
    position, and the greedy tokens must be identical up to a split at a
    near-tie (the one-chip logits rank the two picks within twice the
    largest placement difference measured).  Whether the logits are
    bitwise equal is printed.  Mosaic kernels cannot be partitioned by
    the compiler, so the sharded engine serves the jnp path — and so does
    its one-chip twin."""
    from repro.kvcache import paged as paged_mod
    from repro.launch import serve as launcher
    from repro.launch.mesh import make_serve_mesh
    from repro.models import model

    check(len(devs) >= args.tp, f"--tp {args.tp} needs {args.tp} chips, "
          f"JAX sees {len(devs)}")
    sargs = launcher_args(launcher, paged_mod, jax, args.seed, False,
                          ["--tp", str(args.tp)])
    cfg, params = launcher.build_model(sargs)
    params = keep_every_token(params, jax, jnp)
    prompts = make_prompts(args.seed, cfg.vocab_size)
    log(f"config {cfg.name} (jnp path, int4, routers keeping every "
        f"token): {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.num_heads} heads; {N_REQUESTS} requests of "
        f"{[len(p) for p in prompts]} tokens, {TP_NEW_TOKENS} new")
    mesh = make_serve_mesh(args.tp)
    meter.take()
    tokens = {}
    for label, m in (("one chip", None), (f"tp{args.tp}", mesh)):
        eng = launcher.build_engine(sargs, cfg, params, m)
        res, stats, wall = serve(eng, prompts, TP_NEW_TOKENS)
        check(len(res) == N_REQUESTS, "every request has a result")
        check_results(res, cfg.vocab_size, TP_NEW_TOKENS)
        tokens[label] = [np.asarray(r.tokens) for r in res]
        comp_s, programs, _ = meter.take()
        log(f"{label}: {wall!r} s wall (compile {comp_s!r} s over "
            f"{programs} programs), peak pages {stats.pages_peak}, "
            f"preemptions {stats.preemptions}")
        del eng
        gc.collect()
    a, b = tokens.values()
    l1, lt = (forced_logits(jax, jnp, model, cfg, params, prompts, a, m)
              for m in (None, mesh))
    rel = rel_l2(lt, l1)
    diff = np.abs(lt - l1).max(axis=-1)
    tie = 2 * float(diff.max())
    log(f"logits one chip vs tp{args.tp} at the first served position "
        f"(prefill): relative L2 {float(rel[0, 0])!r} for request 0, worst "
        f"{float(rel[:, 0].max())!r} over the {N_REQUESTS} requests; "
        f"bitwise equal: {bool(np.array_equal(l1, lt))}")
    log(f"logits one chip vs tp{args.tp} over all {rel.size} served "
        f"positions: worst relative L2 {float(rel.max())!r} (bound "
        f"{LOGIT_BOUND}), worst max |diff| {float(diff.max())!r}")
    split = []
    for r, (x, y) in enumerate(zip(a, b)):
        at = np.flatnonzero(x != y)
        if at.size:
            i = int(at[0])
            gap = float(abs(l1[r, i, x[i]] - l1[r, i, y[i]]))
            split.append((r, i, gap))
            log(f"request {r}: greedy tokens split at token {i}; the "
                f"one-chip logits put the two picks {gap!r} apart "
                f"(near-tie bound {tie!r})")
    log(f"greedy tokens identical for {N_REQUESTS - len(split)}/"
        f"{N_REQUESTS} requests (one chip vs tp{args.tp})")
    check(bool((rel <= LOGIT_BOUND).all()),
          f"tp{args.tp} logits differ from one chip by relative L2 "
          f"{float(rel.max())} > {LOGIT_BOUND}")
    far = [(r, i) for r, i, gap in split if gap > tie]
    check(not far, f"tp{args.tp} greedy tokens split from one chip away "
          f"from a near-tie at (request, token) {far}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tp", type=int, default=0,
                    help="run only the tensor-parallel check on N chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    devs = require_tpu(jax)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    d = devs[0]
    log(f"device: {d.platform} {d.device_kind} x{len(devs)}; compile "
        f"cache {cache_dir}")
    meter = CompileMeter(jax.monitoring)
    if args.tp:
        tensor_parallel(args, jax, jnp, devs, meter)
    else:
        one_chip(args, jax, jnp, devs, meter)
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
