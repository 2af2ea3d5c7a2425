"""Faults planted under the timed path make ``correct`` come out false.

Each test skips the harness's look for a chip and drives a whole tiny
run with one fault in the program: a decode step that returns the KV
store unchanged, half of the slots fed the other half's logits, each
token altered where the decode loop produces it, and routers that read
only their bias, so every token follows its router's lean.  (One chip
has no exchange between chips to leave out.)  The last tests put the
control in the program's place: the reference computed in float8, whose greedy
tokens the float32 reference must judge worse than the limit, once on
its own and once through a whole run (``--control 1``)."""
import json

import numpy as np
import pytest

from chipbench import check, reference, spec
from chipbench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("checkout"))


def _result(root):
    rc, lines, err = tiny.run(root)
    assert rc == 0, err
    return json.loads(lines[-1])


def test_store_left_unchanged_is_caught(root, monkeypatch):
    from repro.kvcache import paged

    monkeypatch.setattr(paged, "commit_decode",
                        lambda store, *a, **k: store)
    assert _result(root)["correct"] is False


def test_half_the_slots_left_out_is_caught(root, monkeypatch):
    from repro.models import model

    step = model.paged_decode_step

    def half(*a, **k):
        logits, store, stats = step(*a, **k)
        h = logits.shape[0] // 2
        import jax.numpy as jnp
        return (jnp.concatenate([logits[:h], logits[:logits.shape[0] - h]]),
                store, stats)

    monkeypatch.setattr(model, "paged_decode_step", half)
    assert _result(root)["correct"] is False


def test_token_altered_where_produced_is_caught(root, monkeypatch):
    from repro.serve import sampling

    sample = sampling.split_sample

    def altered(logits, *a, **k):
        rng, tok = sample(logits, *a, **k)
        return rng, (tok + 1) % logits.shape[-1]

    monkeypatch.setattr(sampling, "split_sample", altered)
    assert _result(root)["correct"] is False


def test_router_reading_only_its_bias_is_caught(root, monkeypatch):
    from repro.core import routing

    monkeypatch.setattr(routing, "router_logits",
                        lambda p, x: (0.0 * x[..., :2].astype("float32")
                                      + p["b"]))
    assert _result(root)["correct"] is False


def test_control_through_the_harness_comparison_is_caught(root):
    """``--control 1``: the same run, with the float8 control compared
    in the program's place by the harness's own comparison."""
    rc, lines, err = tiny.run(root, "--control", "1")
    assert rc == 0, err
    res = json.loads(lines[-1])
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["compared"].values())


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_float8_control_fails_the_limit(seed):
    conf = json.loads((tiny.DATA / "tiny.json").read_text())
    rng = np.random.default_rng(seed)
    seqs = [rng.integers(0, conf["vocab_size"], n).astype(np.int32)
            for n in (40, 30, 50)]
    rows = [np.arange(8, len(s)) for s in seqs]
    out = reference.run(conf, spec.weights_key(seed), seqs, rows,
                        lowp_modes=(False, True))
    gap = np.percentile(check.control_gaps(out[False], out[True]), 90)
    assert gap > conf["correct_limit"]["served_logit_gap_p90"]
