"""The chip smoke's own checks, at smoke width on the CPU: the
teacher-forced logits it compares, the decode path it drives, the router
bias it applies and its refusal to start without a TPU."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.kvcache import paged  # noqa: E402
from repro.models import model as M  # noqa: E402

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def smoke():
    cfg = get_config("llama2-7b").smoke()
    params = chip_smoke.keep_every_token(
        M.init_params(KEY, cfg, quantize=True), jax, jnp)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (n,), dtype=np.int32)
               for n in (13, 7)]
    tokens = [rng.integers(0, cfg.vocab_size, (4,), dtype=np.int32)
              for _ in prompts]
    return cfg, params, prompts, tokens


def test_keep_every_token_keeps_every_gate(smoke):
    cfg, params, prompts, _ = smoke
    _, _, stats = M.prefill(params, {"tokens": jnp.asarray(prompts[0])[None]},
                            cfg)
    assert bool((np.asarray(stats["attn_gate"]) == 1.0).all())


def test_forced_logits_are_each_prompts_own_forward(smoke):
    """Each row is the unpadded single-sequence forward at the right
    positions.  The batch's shape changes XLA:CPU's rounding (about 1%
    relative at this width); a pad reaching a compared position or an
    off-by-one position moves the logits by far more."""
    cfg, params, prompts, tokens = smoke
    got = chip_smoke.forced_logits(jax, jnp, M, cfg, params, prompts, tokens)
    assert got.shape == (2, 4, cfg.vocab_size)
    for r, (p, t) in enumerate(zip(prompts, tokens)):
        seq = jnp.asarray(np.concatenate([p, t]))[None]
        ref = np.asarray(M.sequence_logits(params, {"tokens": seq}, cfg)[0],
                         np.float32)[len(p) - 1:len(p) - 1 + len(t)]
        assert float(chip_smoke.rel_l2(got[r], ref).max()) < 0.05


@pytest.mark.parametrize("use_kernels", [False, True])
def test_decode_logits_follow_the_full_forward(smoke, use_kernels):
    """Prefill + pages + teacher-forced paged decode steps give the
    next-token logits of one full forward over the same tokens."""
    cfg, params, prompts, tokens = smoke
    c = dataclasses.replace(cfg, use_kernels=use_kernels)
    got = chip_smoke.decode_logits(jax, jnp, M, paged, c, params,
                                   prompts[0], tokens[0])
    want = chip_smoke.forced_logits(jax, jnp, M, cfg, params, prompts[:1],
                                    tokens[:1])[0]
    assert got.shape == want.shape
    assert float(chip_smoke.rel_l2(got, want).max()) < chip_smoke.LOGIT_BOUND


def test_require_tpu_refuses_the_cpu():
    with pytest.raises(SystemExit, match="needs a TPU"):
        chip_smoke.require_tpu(jax)
