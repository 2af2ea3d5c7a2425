"""The plain reference against the program at the smoke size of both
benchmark configurations: the same weights from the same seed, and
next-token logits that agree up to the program's bfloat16 rounding."""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, reference, spec, weights

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"
DENSE = spec.load_family(CONFIGS.parents[1], "dense_gqa")


def smoke_conf(name):
    """The configuration file at the registry's smoke size, its published
    keys rewritten to the smoke widths."""
    conf = json.loads((CONFIGS / f"{name}.json").read_text())
    conf["repro"] = dict(conf["repro"], smoke=True)
    conf["repro"]["overrides"] = {"norm_eps": conf["rms_norm_eps"]}
    from repro.configs import get_config
    cfg = get_config(conf["repro"]["arch"]).smoke()
    conf.update(hidden_size=cfg.d_model, intermediate_size=cfg.d_ff,
                num_hidden_layers=cfg.num_layers,
                num_attention_heads=cfg.num_heads,
                num_key_value_heads=cfg.num_kv_heads,
                head_dim=cfg.resolved_head_dim, vocab_size=cfg.vocab_size)
    return conf


@pytest.mark.parametrize("name", ["qwen3-8b", "deepseek-coder-33b-16l"])
def test_reference_matches_program_logits(name):
    from repro.models import model

    conf = smoke_conf(name)
    cfg = spec.model_config(conf, DENSE)
    key = spec.weights_key(2**31 + 3)
    params = weights.program_params(key, weights.dims_of(conf))
    toks = np.random.default_rng(1).integers(
        0, cfg.vocab_size, 48).astype(np.int32)
    prog = np.asarray(model.sequence_logits(
        params, {"tokens": jnp.asarray(toks)[None]}, cfg)[0], np.float32)
    res = reference.run(conf, key, [toks], [np.arange(48)])[False]
    ref = np.asarray(res.logits[0])
    rel = np.linalg.norm(prog - ref) / np.linalg.norm(ref)
    # bfloat16 activations in the program, float32 in the reference
    assert rel < 0.03, rel
    assert check.gaps(res.logits[0], prog.argmax(axis=1)).max() < 0.01


@pytest.mark.parametrize("name", ["qwen3-8b", "deepseek-coder-33b-16l"])
def test_reference_weights_are_the_programs(name):
    from repro.models import model
    from repro.quant.int4 import dequantize

    conf = smoke_conf(name)
    cfg = spec.model_config(conf, DENSE)
    key = spec.weights_key(7)
    params = model.init_params(key, cfg, quantize=True)
    dm = reference.dims_of(conf)
    ka, km = weights.keep_masks(key, dm.layers)
    w = reference.layer_weights(weights.layer_keys(key, dm.layers)[1], dm,
                                jnp.float32(1), ka[1], km[1])
    blk = jax.tree_util.tree_map(lambda a: a[0],
                                 params["stack"]["stages"])["pos0"]

    def dense(p):
        if "w_int" in p:
            return np.asarray(dequantize(p["w_int"], p["scale"]))
        return np.asarray(p["w"], np.float32)

    for mine, theirs in [("wqkv", blk["mixer"]["inner"]["wqkv"]),
                         ("wo", blk["mixer"]["inner"]["wo"]),
                         ("gu", blk["ffn"]["inner"]["gu"]),
                         ("down", blk["ffn"]["inner"]["down"])]:
        np.testing.assert_array_equal(np.asarray(w[mine]), dense(theirs))
    np.testing.assert_array_equal(
        np.asarray(reference.head_weight(key, dm)), dense(params["lm_head"]))


@pytest.mark.parametrize("name", ["qwen3-8b", "deepseek-coder-33b-16l"])
def test_program_params_equal_the_programs_own_init(name):
    """The benchmark draws the served weights itself, in one jitted call:
    every matrix, gain and table is the program's
    ``init_params(quantize=True)`` leaf for leaf but the routers: a
    keep-minus-skip bias of +1 or -1, a random keep column and a zero
    skip column."""
    from repro.models import model

    conf = smoke_conf(name)
    cfg = spec.model_config(conf, DENSE)
    key = spec.weights_key(2**31 + 11)
    mine = weights.program_params(key, weights.dims_of(conf))
    theirs = model.init_params(key, cfg, quantize=True)
    assert (jax.tree_util.tree_structure(mine)
            == jax.tree_util.tree_structure(theirs))
    flat = jax.tree_util.tree_flatten_with_path(mine)[0]
    biases = []
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(theirs)):
        assert a.dtype == b.dtype and a.shape == b.shape
        names = [getattr(k, "key", "") for k in path]
        if "router" in names:
            if names[-1] == "w":
                w = np.asarray(a)
                assert not w[..., 0].any() and np.abs(w[..., 1]).min() > 0
            else:
                biases.append(np.asarray(a).reshape(-1, 2))
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    b = np.concatenate(biases)
    assert set(map(tuple, b.tolist())) <= {(0.0, 1.0), (0.0, -1.0)}


@pytest.mark.parametrize("name", ["qwen3-8b", "deepseek-coder-33b-16l"])
def test_routers_decide_per_token(name):
    """Most tokens follow their router's lean and some go against it, so
    a layer's gates differ from token to token; the kept share stays
    near the leaning share."""
    conf = smoke_conf(name)
    dm = reference.dims_of(conf)
    rng = np.random.default_rng(4)
    seqs = [rng.integers(0, dm.vocab, n).astype(np.int32)
            for n in (300, 200)]
    key = spec.weights_key(2**31 + 21)
    res = reference.run(conf, key, seqs, [np.arange(4)] * 2)[False]
    assert 0.001 < res.against < 0.1, res.against
    attn, mlp = (float(np.mean(m)) for m in weights.keep_masks(key, dm.layers))
    assert abs(res.attn_keep - attn) < 0.1 and abs(res.mlp_keep - mlp) < 0.1
    # every token stores its layer-0 entry, and those of the later
    # layers whose gate it opens
    for s, e in zip(seqs, res.entries):
        assert len(s) <= e <= len(s) * dm.layers


@pytest.mark.parametrize("layers", [2, 16, 36])
def test_routers_keep_a_fixed_share_at_seeded_layers(layers):
    counts = set()
    for seed in (1, 2, 2**31 + 7):
        attn, mlp = (np.asarray(m) for m in weights.keep_masks(
            spec.weights_key(seed), layers))
        assert attn[0]
        counts.add((int(attn.sum()), int(mlp.sum())))
    assert counts == {(1 + round(0.75 * (layers - 1)),
                       round(0.75 * layers))}


def test_program_params_layout_at_full_width():
    """At qwen3-8b's full width the tree has the program's structure,
    shapes and types (shapes only: nothing is drawn)."""
    from repro.models import model

    conf = json.loads((CONFIGS / "qwen3-8b.json").read_text())
    cfg = spec.model_config(conf, DENSE)
    key = jax.random.PRNGKey(0)
    mine = jax.eval_shape(lambda k: weights.program_params(
        k, weights.dims_of(conf)), key)
    theirs = jax.eval_shape(
        lambda k: model.init_params(k, cfg, quantize=True), key)
    assert (jax.tree_util.tree_structure(mine)
            == jax.tree_util.tree_structure(theirs))
    for a, b in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(theirs)):
        assert (a.dtype, a.shape) == (b.dtype, b.shape)
