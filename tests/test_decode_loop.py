"""Device-resident N-step decode epochs (``model.decode_loop`` /
``model.paged_decode_loop`` and the engine's ``decode_steps > 1`` mode).

Acceptance bar: the fused loops are a pure dispatch-granularity change —
token output must be bit-identical to the single-step engine (greedy)
across dense/paged KV, chunked prefill, forced preemption and kernels,
with strictly fewer jitted decode dispatches; and a slot that finishes
mid-epoch must stop appending KV *inside* the scan (frozen (feed, t)
carry dense-side, commit-mask drop paged-side).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.routing import neutral_router_bias
from repro.models import model as M
from repro.serve.engine import ContinuousBatchingEngine, init_pool, \
    pool_insert
from repro.serve.sampling import split_sample
from repro.serve.scheduler import Scheduler, StepPlan

KEY = jax.random.PRNGKey(0)


def _cfg(name="llama2-7b", **over):
    cfg = get_config(name).smoke()
    return dataclasses.replace(cfg, **over) if over else cfg


def _params(cfg):
    # neutral bias: the router actually skips, so the gate log (and the
    # KV freeze it drives) is exercised, not just the dense fast path
    return neutral_router_bias(M.init_params(KEY, cfg))


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (l,), dtype=np.int32)
            for l in lens]


# ---------------------------------------------------------------------------
# Model level: the scan must replay the single-step path exactly.
# ---------------------------------------------------------------------------

def _seed_pool(cfg, params, prompts, max_len):
    """Prefill each prompt alone and scatter into a slot pool; returns
    (pool, first-token feed, positions)."""
    pool = init_pool(cfg, len(prompts), max_len)
    feed, pos = [], []
    for s, p in enumerate(prompts):
        lg, cache, _ = M.prefill(params, {"tokens": jnp.asarray(p[None])},
                                 cfg, pad_to=max_len)
        pool = pool_insert(pool, cache, s, cfg)
        feed.append(int(jnp.argmax(lg[0])))
        pos.append(len(p))
    return pool, np.asarray(feed, np.int32), np.asarray(pos, np.int32)


def test_decode_loop_matches_sequential_steps():
    """n_steps fused iterations == n sequential decode_step + sample calls:
    same tokens, same final cache, same rng stream."""
    cfg = _cfg()
    params = _params(cfg)
    max_len, n = 24, 5
    prompts = _prompts(cfg, [6, 9])
    pool, feed, pos = _seed_pool(cfg, params, prompts, max_len)
    ref_pool = pool                              # eager calls don't donate
    B = len(prompts)
    act = np.ones((B,), bool)
    budget = np.full((B,), n + 1, np.int32)      # no one finishes early
    stop = np.full((B,), -1, np.int32)
    rng = jax.random.PRNGKey(3)

    new_pool, out = M.decode_loop(params, pool, feed, pos, act, budget,
                                  stop, rng, n_steps=n, cfg=cfg,
                                  max_len=max_len)
    toks = np.asarray(out["tokens"])                       # [n, B]

    step = jax.jit(lambda p, c, f, t: M.decode_step(
        p, c, {"tokens": f[:, None]}, t, cfg))
    f, t = jnp.asarray(feed), jnp.asarray(pos)
    for i in range(n):
        logits, ref_pool, _ = step(params, ref_pool, f, t)
        rng, tok = split_sample(logits, rng)
        np.testing.assert_array_equal(toks[i], np.asarray(tok))
        f, t = tok, t + 1
    np.testing.assert_array_equal(np.asarray(out["feed"]), np.asarray(f))
    np.testing.assert_array_equal(np.asarray(out["t"]), np.asarray(t))
    assert np.asarray(out["step_active"]).all()
    for a, b in zip(jax.tree_util.tree_leaves(new_pool),
                    jax.tree_util.tree_leaves(ref_pool)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_decode_loop_mid_stop_freezes_kv():
    """A slot finishing mid-scan (stop token sampled) must freeze its
    (feed, t) carry: positions past its stop point stay untouched in the
    cache — the finished slot stops appending KV with the other slot
    still decoding."""
    cfg = _cfg()
    params = _params(cfg)
    max_len, n = 24, 6
    prompts = _prompts(cfg, [6, 9])
    pool, feed, pos = _seed_pool(cfg, params, prompts, max_len)
    k_init = np.asarray(pool["stage0"]["pos0"]["k"])       # [S, T, H, d]
    B = len(prompts)
    act = np.ones((B,), bool)
    budget = np.full((B,), n + 1, np.int32)
    rng = jax.random.PRNGKey(3)

    # free-running reference epoch → pick slot 0's mid-epoch token as the
    # stop token (whatever its first occurrence is)
    free_pool, ref = M.decode_loop(params, pool, feed, pos, act, budget,
                                   np.full((B,), -1, np.int32), rng,
                                   n_steps=n, cfg=cfg, max_len=max_len)
    ref_toks = np.asarray(ref["tokens"])                   # [n, B]
    stop_tok = int(ref_toks[2, 0])
    k_stop = int(np.argmax(ref_toks[:, 0] == stop_tok))   # first hit
    assert k_stop < n - 1, "stop must fire mid-epoch for the test to bite"
    if stop_tok in ref_toks[:, 1]:
        pytest.skip("stop token collides with the other slot's stream")

    stop = np.asarray([stop_tok, -1], np.int32)
    new_pool, out = M.decode_loop(params, pool, feed, pos, act,
                                  budget, stop, rng, n_steps=n, cfg=cfg,
                                  max_len=max_len)
    sa = np.asarray(out["step_active"])                    # [n, B]
    assert sa[:k_stop + 1, 0].all() and not sa[k_stop + 1:, 0].any()
    assert sa[:, 1].all()
    # slot 0's tokens match the free run up to (and including) the stop
    np.testing.assert_array_equal(np.asarray(out["tokens"])[:k_stop + 1, 0],
                                  ref_toks[:k_stop + 1, 0])
    # position carry froze at the stop point
    t_stop = int(pos[0]) + k_stop
    assert int(np.asarray(out["t"])[0]) == t_stop
    assert not bool(np.asarray(out["active"])[0])
    # the KV row stopped growing: positions past t_stop are untouched
    # (bit-identical to the pre-loop pool), while the free-running epoch
    # overwrote them — and the live slot kept appending in both
    k_frozen = np.asarray(new_pool["stage0"]["pos0"]["k"])
    k_free = np.asarray(free_pool["stage0"]["pos0"]["k"])
    np.testing.assert_array_equal(k_frozen[0, t_stop + 1:],
                                  k_init[0, t_stop + 1:])
    np.testing.assert_array_equal(k_frozen[0, :t_stop + 1],
                                  k_free[0, :t_stop + 1])
    assert not np.array_equal(k_free[0, t_stop + 1: int(pos[0]) + n],
                              k_init[0, t_stop + 1: int(pos[0]) + n])
    np.testing.assert_array_equal(k_frozen[1], k_free[1])


# ---------------------------------------------------------------------------
# Engine level: fused epochs vs the single-step engine, bit for bit.
# ---------------------------------------------------------------------------

def _run(cfg, params, prompts, budgets, stop_token=None, **kw):
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2, max_len=48,
                                   **kw)
    uids = [eng.submit(p, n, stop_token=stop_token)
            for p, n in zip(prompts, budgets)]
    return eng, uids, eng.run(jax.random.PRNGKey(7))


def _assert_identical(ref, fused):
    for uid in ref["results"]:
        a, b = ref["results"][uid], fused["results"][uid]
        np.testing.assert_array_equal(a.tokens, b.tokens)
        assert a.finish_reason == b.finish_reason
        assert (a.kv_stored, a.kv_dense) == (b.kv_stored, b.kv_dense)
    sa, sb = ref["stats"], fused["stats"]
    assert sa.decode_tokens == sb.decode_tokens
    assert sa.requests_completed == sb.requests_completed
    assert sa.kv_saved_fraction == pytest.approx(sb.kv_saved_fraction)
    assert sb.decode_dispatches < sa.decode_dispatches


@pytest.mark.parametrize("kv_mode,chunk,n_steps", [
    ("dense", 0, 4),
    ("dense", 8, 8),
    ("paged", 0, 8),
    ("paged", 8, 8),
])
def test_fused_engine_token_identity(kv_mode, chunk, n_steps):
    """N-step epochs emit the exact single-step token streams — mixed
    budgets (incl. max_new=1), a stop token that fires mid-run, chunked
    prefill interleaving, both KV modes — with fewer dispatches."""
    cfg = _cfg()
    params = _params(cfg)
    prompts = _prompts(cfg, [10, 5, 9, 14, 7])
    budgets = [6, 1, 9, 4, 7]
    _, _, ref = _run(cfg, params, prompts, budgets, stop_token=9,
                     kv_mode=kv_mode, prefill_chunk=chunk)
    enf, _, fused = _run(cfg, params, prompts, budgets, stop_token=9,
                         kv_mode=kv_mode, prefill_chunk=chunk,
                         decode_steps=n_steps)
    _assert_identical(ref, fused)
    if kv_mode == "paged":
        # device-side fill advance replayed host-side: every page returned
        assert enf.allocator.free_pages == enf.num_pages


def test_fused_engine_identity_with_kernels():
    cfg = _cfg(use_kernels=True)
    params = _params(cfg)
    prompts = _prompts(cfg, [10, 14, 6])
    _, _, ref = _run(cfg, params, prompts, [6, 8, 4], stop_token=9)
    _, _, fused = _run(cfg, params, prompts, [6, 8, 4], stop_token=9,
                       decode_steps=8)
    _assert_identical(ref, fused)


def test_fused_paged_preemption_identity():
    """Page pressure inside fused mode: the epoch first shrinks, then
    preempts (youngest-first) — and the token streams still match the
    dense single-step engine exactly."""
    cfg = _cfg()
    params = _params(cfg)
    prompts = _prompts(cfg, [8, 8], seed=1)
    _, ud, ref = _run(cfg, params, prompts, [24, 24])
    # 8 pages: enough spare for both prompts to be admitted concurrently
    # (6 would make the epoch reservation defer the second admission and
    # dodge preemption entirely), yet too few for both to finish resident.
    # 24 new tokens each: at 16 the two requests' measured entries (after
    # history reuse) fit in 8 pages once the epoch shrinks to one step, so
    # a correct engine never has to preempt.
    eng, up, fused = _run(cfg, params, prompts, [24, 24], kv_mode="paged",
                          page_size=8, num_pages=8, decode_steps=8)
    assert fused["stats"].preemptions >= 1
    assert fused["stats"].requests_completed == 2
    for a, b in zip(ud, up):
        np.testing.assert_array_equal(ref["results"][a].tokens,
                                      fused["results"][b].tokens)
    assert eng.allocator.free_pages == eng.num_pages


def test_fused_deferred_first_token_stop():
    """Dense fused mode defers first tokens on device; when that deferred
    token IS the stop token the slot must be entry-killed inside the loop
    (no emissions, no KV appends) and finished with reason "stop" — the
    exact single-step completion-path behaviour."""
    cfg = _cfg()
    params = _params(cfg)
    prompts = _prompts(cfg, [10, 7])
    # discover request 0's first token from an unconstrained run
    _, uids, probe = _run(cfg, params, prompts, [4, 6])
    first_tok = int(probe["results"][uids[0]].tokens[0])
    _, ur, ref = _run(cfg, params, prompts, [4, 6], stop_token=first_tok)
    _, uf, fused = _run(cfg, params, prompts, [4, 6], stop_token=first_tok,
                        decode_steps=8)
    assert ref["results"][ur[0]].finish_reason == "stop"
    assert len(ref["results"][ur[0]].tokens) == 1
    _assert_identical(ref, fused)


def test_prefill_kv_accounting_measured():
    """Warm-start measured-saving regression (the bench anomaly): with
    max_new_tokens=1 there are no decode steps, so any measured saving
    must come from the *prompt-phase* gate log — which used to be dropped
    on the floor (measured 0.000 vs analytic 0.125).  With a skipping
    router it must now land in the paper's regime; both KV modes agree."""
    cfg = _cfg()
    params = _params(cfg)
    prompts = _prompts(cfg, [10, 14, 6])
    fracs = []
    for mode in ("dense", "paged"):
        _, _, out = _run(cfg, params, prompts, [1, 1, 1], kv_mode=mode)
        s = out["stats"]
        assert 0.0 < s.kv_saved_fraction < 0.5, mode
        for r in out["results"].values():
            assert r.kv_dense > 0
        fracs.append(s.kv_saved_fraction)
    assert fracs[0] == pytest.approx(fracs[1])


def test_warmstart_keeps_everything_measured_zero():
    """The flip side: warm-started router biases keep every token, so the
    *measured* saving is genuinely 0.0 (the analytic figure is an
    estimate, not ground truth) — pin it so the bench row's meaning
    stays documented."""
    cfg = _cfg()
    params = M.init_params(KEY, cfg)             # warm-start bias
    _, _, out = _run(cfg, params, _prompts(cfg, [10, 14]), [4, 4])
    assert out["stats"].kv_saved_fraction == 0.0
    assert out["stats"].kv_saved_analytic > 0.0


# ---------------------------------------------------------------------------
# Scheduler + config plumbing
# ---------------------------------------------------------------------------

def test_plan_step_epoch_token_budget():
    """Each decode slot costs ``decode_steps`` budget tokens: a chunk that
    fits alongside single-step decodes is deferred under an N-step epoch
    (but never twice — the anti-starvation rule is epoch-agnostic)."""
    assert StepPlan(decode_slots=[0, 1], prefill=None,
                    decode_steps=8).tokens == 16
    sched = Scheduler(max_slots=4, max_len=64, prefill_chunk=8)
    from repro.serve.scheduler import ActiveRequest, Request
    for slot in (0, 1):
        req = Request(uid=slot, tokens=np.zeros((4,), np.int32),
                      max_new_tokens=4)
        sched._free.remove(slot)
        sched.active[slot] = ActiveRequest(
            req=req, slot=slot, pos=4, next_token=0, out_tokens=[0],
            submit_s=0.0, first_token_s=0.0)
    sched.submit(Request(uid=9, tokens=np.zeros((8,), np.int32),
                         max_new_tokens=4))
    # budget 12: 2 slots × 1 step + 8-token chunk = 10 fits single-step
    plan = sched.plan_step(token_budget=12, decode_steps=1)
    assert plan.prefill is not None and plan.tokens <= 12
    sched.abort_prefill()
    sched.submit(Request(uid=10, tokens=np.zeros((8,), np.int32),
                         max_new_tokens=4))
    # same budget, 8-step epoch: 2 × 8 + 8 = 24 > 12 → deferred once...
    plan = sched.plan_step(token_budget=12, decode_steps=8)
    assert plan.prefill is None
    assert plan.decode_steps == 8
    # ...but not twice (prefill must not starve)
    plan = sched.plan_step(token_budget=12, decode_steps=8)
    assert plan.prefill is not None


def test_decode_steps_validation_and_config_default():
    cfg = _cfg()
    params = M.init_params(KEY, cfg)
    with pytest.raises(ValueError, match="decode_steps"):
        ContinuousBatchingEngine(cfg, params, max_slots=2, max_len=32,
                                 decode_steps=0)
    cfg8 = dataclasses.replace(cfg, decode_steps_per_dispatch=8)
    eng = ContinuousBatchingEngine(cfg8, params, max_slots=2, max_len=32)
    assert eng.decode_steps == 8                 # cfg lever is the default
    eng = ContinuousBatchingEngine(cfg8, params, max_slots=2, max_len=32,
                                   decode_steps=1)
    assert eng.decode_steps == 1                 # ctor arg overrides
