"""Architecture families: the dense GQA family is the weights, reference
and counts it binds, exactly; a family added as a new file runs end to
end and decides ``correct`` with its own reference; the per-layer readers
read their counts from the family; a configuration that names no known
family is refused before any device work."""
import json
import pathlib
import types

import jax
import numpy as np
import pytest

from chipbench import counts, reference, spec, weights
from chipbench.tests import tiny
from chipbench.trace import TPU_KERNEL

ROOT = pathlib.Path(__file__).resolve().parents[2]
DENSE = spec.load_family(ROOT, "dense_gqa")
CONFIGS = ["qwen3-8b", "deepseek-coder-33b-16l"]
PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9}


def conf_of(name):
    return json.loads((ROOT / "chipbench" / "configs" / f"{name}.json")
                      .read_text())


def grid(dm):
    """Rows, contexts, keep shares and leaning masks, sampled."""
    rng = np.random.default_rng(dm.layers)
    for ctx in (1, 517, 2048.5):
        for keep in ((1.0, 1.0), (0.7639, 0.7461), (0.25, 0.9)):
            lean = tuple(rng.random(dm.layers) < 0.75 for _ in range(2))
            for m in (1, 16, 1024):
                yield ctx, keep, lean, m


def _linears(c, dm, lean, keep, m):
    return [c.linear_call(lin, m * s)
            for (lin, _), s in zip(counts.kept_linears(dm, lean),
                                   DENSE.row_share(dm, lean, keep))]


# each count through the family, and as the readers called it before
# there were families
COUNTS = {
    "token_flops": lambda c, dm, ctx, keep, lean, m: [
        c.token_flops(dm, ctx, *keep, logits) for logits in (False, True)],
    "prefill_logit_ops": lambda c, dm, *_: (
        c.prefill_logit_ops(dm) if c is DENSE else 2 * dm.d * dm.vocab),
    "decode_step_least_s": lambda c, dm, ctx, keep, lean, m:
        c.decode_step_least_s(dm, PEAKS, m, ctx * m * dm.layers * 0.8,
                              ctx * m, *keep, lean),
    "kept_linears": lambda c, dm, ctx, keep, lean, m: c.kept_linears(dm,
                                                                     lean),
    "linear_call": lambda c, dm, ctx, keep, lean, m: _linears(c, dm, lean,
                                                              keep, m),
    "row_share": lambda c, dm, ctx, keep, lean, m: (
        list(c.row_share(dm, lean, keep)) if c is DENSE else
        [c.row_share(dm, lean, keep)[i // 2]
         for i, _ in enumerate(c.kept_linears(dm, lean))]),
    "paged_attention_call": lambda c, dm, ctx, keep, lean, m:
        c.paged_attention_call(dm, ctx * m, m, keep[0]),
    "kv_entry_bytes": lambda c, dm, *_: c.kv_entry_bytes(dm),
    "model_weight_bytes": lambda c, dm, ctx, keep, lean, m:
        c.model_weight_bytes(dm, lean),
}


@pytest.mark.parametrize("count", sorted(COUNTS))
@pytest.mark.parametrize("name", CONFIGS)
def test_dense_family_counts_equal_the_counts_module(name, count):
    dm = DENSE.dims_of(conf_of(name))
    assert dm == weights.dims_of(conf_of(name))
    for point in grid(dm):
        assert (COUNTS[count](DENSE, dm, *point)
                == COUNTS[count](counts, dm, *point)), (count, point)


@pytest.mark.parametrize("name", CONFIGS)
def test_dense_family_draws_the_same_tree_and_masks(name):
    """At the configuration's full width, shapes only (nothing is drawn);
    the leaning masks at its depth."""
    dm = DENSE.dims_of(conf_of(name))
    key = spec.weights_key(2**31 + 17)
    mine = jax.eval_shape(lambda k: DENSE.program_params(k, dm), key)
    theirs = jax.eval_shape(lambda k: weights.program_params(k, dm), key)
    assert mine == theirs
    for a, b in zip(DENSE.keep_masks(key, dm.layers),
                    weights.keep_masks(key, dm.layers)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dense_family_reference_is_the_reference():
    conf = json.loads((tiny.DATA / "tiny.json").read_text())
    key = spec.weights_key(2**31 + 19)
    rng = np.random.default_rng(5)
    seqs = [rng.integers(0, conf["vocab_size"], n).astype(np.int32)
            for n in (20, 33)]
    rows = [np.arange(4, len(s)) for s in seqs]
    mine, facts = DENSE.reference_run(conf, key, seqs, rows, (False, True))
    theirs = reference.run(conf, key, seqs, rows, (False, True))
    assert facts == {} and set(mine) == {False, True}
    for lowp in (False, True):
        a, b = mine[lowp], theirs[lowp]
        assert a._replace(logits=None) == b._replace(logits=None)
        for x, y in zip(a.logits, b.logits):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    tree = DENSE.program_params(key, DENSE.dims_of(conf))
    same = weights.program_params(key, weights.dims_of(conf))
    assert all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(
        jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(same)))


# A family added as a new file: dense GQA drawn from another key, in its
# program and in its reference alike.  REF_KEY is the key its reference
# draws from; the faulty copy's reference draws the dense family's own.
TOY = '''"""Dense GQA whose weights are drawn from another key."""
import jax

from chipbench.families import dense_gqa as dense
from chipbench.families.dense_gqa import (  # noqa: F401
    decode_step_least_s, dims_of, hf_keys, kept_linears, kv_entry_bytes,
    linear_call, model_weight_bytes, paged_attention_call,
    prefill_logit_ops, row_share, token_flops)


def other(key):
    return jax.random.fold_in(key, 7)


REF_KEY = {ref_key}


def program_params(key, dims):
    return dense.program_params(other(key), dims)


def keep_masks(key, layers):
    return dense.keep_masks(other(key), layers)


def reference_run(conf, key, seqs, rows, lowp_modes=(False,)):
    out, facts = dense.reference_run(conf, REF_KEY(key), seqs, rows,
                                     lowp_modes)
    return out, dict(facts, toy=True)
'''


def add_config(root: pathlib.Path, family: str, ref_key: str = "other"):
    """The toy family, a configuration naming it and a cell running it,
    added to a tiny checkout as new files and new entries."""
    (root / "chipbench" / "families" / f"{family}.py").write_text(
        TOY.format(ref_key=ref_key))
    conf = json.loads((tiny.DATA / "tiny.json").read_text())
    conf.update(name=f"tiny-{family}", family=family)
    (root / "chipbench" / "configs" / f"tiny-{family}.json").write_text(
        json.dumps(conf))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": conf["name"], "source": "test",
                             "file": f"chipbench/configs/{conf['name']}.json",
                             "reduced": [], "why": "test"})
    cell = f"{conf['name']}.chat"
    bench["workloads"].append({"name": cell, "config": conf["name"],
                               "traffic": "tiny-chat", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return cell


@pytest.mark.parametrize("ref_key,correct", [("other", True),
                                             ("lambda k: k", False)],
                         ids=["own_reference", "reference_differs"])
def test_family_added_as_a_file_runs_and_decides_correct(
        tmp_path, ref_key, correct):
    """Found by name and run end to end with no edit to a file of the
    checkout.  Its reference is the one the check uses: one that draws
    the weights the program was not given makes the run incorrect."""
    root = tiny.make_root(tmp_path)
    before = {p: p.read_bytes() for p in root.rglob("*")
              if p.is_file() and p.name != "BENCHMARK.json"}
    cell = add_config(root, "toy_dense", ref_key)
    rc, lines, err = tiny.run(root, cell=cell, seconds=1.0)
    assert rc == 0, err
    res = json.loads(lines[-1])
    assert res["correct"] is correct, res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert all(p.read_bytes() == b for p, b in before.items())


def _op(kind):
    if kind == "fused_linear":
        return f"%x = s8[1] custom-call(s8[128,256]), {TPU_KERNEL}"
    return f"%y = (f32[2,4]) custom-call(s32[16,8]), {TPU_KERNEL}"


def reader_ctx(family):
    """A traced window at qwen3-8b's width: two requests, one prefilled
    in the window and one only decoding there, two decode epochs, one
    prefill, and a second of each program on the device."""
    dm = DENSE.dims_of(conf_of("qwen3-8b"))
    lean = tuple(np.asarray(m) for m in DENSE.keep_masks(
        spec.weights_key(3), dm.layers))
    reqs = [types.SimpleNamespace(prompt=np.zeros(600), times=[1.0, 2.0,
                                                               3.0]),
            types.SimpleNamespace(prompt=np.zeros(900), times=[0.1, 1.5])]
    epochs = [types.SimpleNamespace(n=8, residents=r, ctx_sum=c, entries=e)
              for r, c, e in ((16, 20_000, 500_000), (12, 15_000, 390_000))]
    return types.SimpleNamespace(
        family=family, dims=dm, facts={}, peaks=PEAKS, slots=16,
        trace={"window_s": 10.0, "modules": {"jit_loop_fn": 1.0},
               "ops": {_op("fused_linear"): 1.0,
                       _op("paged_attention"): 1.0}},
        epochs=epochs, prefills=[(600, 1024)], snaps=[], queue_waits=[],
        counters={}, reqs=reqs, t_open=0.5, t_close=5.0,
        keep=(0.76, 0.74), lean=lean)


def doubled(*names):
    """The dense family with the counts ``names`` doubled: operations and
    bytes alike."""
    def twice(f):
        def g(*a):
            v = f(*a)
            return tuple(2 * x for x in v) if isinstance(v, tuple) else 2 * v
        return g
    fam = types.SimpleNamespace(**{k: getattr(DENSE, k) for k in dir(DENSE)
                                   if not k.startswith("_")})
    for n in names:
        setattr(fam, n, twice(getattr(DENSE, n)))
    return fam


@pytest.mark.parametrize("metric,names", [
    ("mfu", ("token_flops", "prefill_logit_ops")),
    ("decode_step_roofline", ("decode_step_least_s",)),
    ("fused_linear_roofline", ("linear_call",)),
    ("paged_attention_roofline", ("paged_attention_call",)),
])
def test_readers_read_their_counts_from_the_family(metric, names):
    from chipbench import run as harness

    mod = harness.load_metric(ROOT, metric)
    base = mod.read(reader_ctx(DENSE))
    assert base is not None and base > 0
    assert mod.read(reader_ctx(doubled(*names))) == 2 * base


@pytest.mark.parametrize("family", ["no_such_family", None, "../configs/x"])
def test_unknown_or_missing_family_is_refused_naming_those_found(
        tmp_path, monkeypatch, family):
    """Before JAX is asked for a device, with the families there are."""
    def no_device(*a, **k):
        raise AssertionError("a device was asked for")

    monkeypatch.setattr(jax, "devices", no_device)
    root = tiny.make_root(tmp_path)
    path = root / "chipbench" / "configs" / "tiny.json"
    conf = json.loads(path.read_text())
    if family is None:
        del conf["family"]
    else:
        conf["family"] = family
    path.write_text(json.dumps(conf))
    with pytest.raises(SystemExit) as e:
        tiny.run(root)
    msg = str(e.value.code)
    assert repr(family) in msg and "['dense_gqa']" in msg
