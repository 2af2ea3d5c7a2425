"""The traffic generator: deterministic from the seed, inside the mix's
bounds, and the same multiset of lengths for every seed."""
import pathlib

import numpy as np
import pytest

from chipbench import loadgen

TRAFFIC = pathlib.Path(__file__).resolve().parents[1] / "traffic"
MIXES = sorted(p.stem for p in TRAFFIC.glob("*.json"))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = loadgen.load_mix(TRAFFIC / f"{name}.json")
    a = loadgen.RequestPool(mix, 2**31 + 17, 1000)
    b = loadgen.RequestPool(mix, 2**31 + 17, 1000)
    for _ in range(20):
        (pa, na), (pb, nb) = a.next(), b.next()
        assert na == nb and np.array_equal(pa, pb)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_within_bounds_and_vocab(name):
    mix = loadgen.load_mix(TRAFFIC / f"{name}.json")
    pool = loadgen.RequestPool(mix, 5, 777)
    lo, hi = mix["prompt_len"]
    olo, ohi = mix["output_len"]
    for _ in range(200):
        prompt, n = pool.next()
        assert lo <= len(prompt) <= hi and olo <= n <= ohi
        assert prompt.dtype == np.int32
        assert 0 <= prompt.min() and prompt.max() < 777
    assert loadgen.max_len(mix) == hi + ohi


def test_seeds_share_the_work_and_differ_in_tokens():
    """Every seed serves the same lengths in the same order; the seed
    draws the token ids."""
    mix = {"clients": 4, "prompt_len": [16, 4096], "output_len": [1, 9],
           "pool": 257}
    a = loadgen.RequestPool(mix, 1, 50)
    b = loadgen.RequestPool(mix, 2, 50)
    assert list(a.prompt_lens) == list(b.prompt_lens)
    assert list(a.output_lens) == list(b.output_lens)
    assert sorted(a.prompt_lens) == sorted(
        loadgen.stratified_lengths(16, 4096, 257))
    assert not np.array_equal(a.next()[0], b.next()[0])


def test_stratified_lengths_are_log_uniform_quantiles():
    x = loadgen.stratified_lengths(100, 10000, 4)
    assert list(x) == [178, 562, 1778, 5623]
    assert loadgen.stratified_lengths(7, 7, 3).tolist() == [7, 7, 7]


def test_bad_mix_is_refused(tmp_path):
    p = tmp_path / "m.json"
    p.write_text('{"clients": 1, "prompt_len": [9, 3], "output_len": [1, 2],'
                 ' "pool": 4}')
    with pytest.raises(ValueError):
        loadgen.load_mix(p)
    p.write_text('{"clients": 1}')
    with pytest.raises(ValueError):
        loadgen.load_mix(p)


def test_residual_budgets_by_hand():
    """One output length L: a request in progress has 1..L tokens left
    alike, so four clients start at the quartiles' midpoints."""
    mix = {"clients": 4, "prompt_len": [1, 1], "output_len": [4, 4],
           "pool": 3}
    assert loadgen.residual_budgets(mix) == [1, 2, 3, 4]
    # lengths 2 and 6: F(r) = (min(r, 2) + min(r, 6)) / 8
    mix = {"clients": 2, "prompt_len": [1, 1], "output_len": [2, 6],
           "pool": 2}
    lens = loadgen.stratified_lengths(2, 6, 2).tolist()
    assert lens == [3, 5]
    # F(1) = 2/8, F(2) = 4/8, F(3) = 6/8, F(4) = 7/8: quantiles 1/4, 3/4
    assert loadgen.residual_budgets(mix) == [1, 3]


@pytest.mark.parametrize("name", MIXES)
def test_residual_budgets_within_the_mix(name):
    mix = loadgen.load_mix(TRAFFIC / f"{name}.json")
    b = loadgen.residual_budgets(mix)
    assert len(b) == mix["clients"] and b == sorted(b)
    assert 1 <= b[0] and b[-1] <= mix["output_len"][1]
    # a request in progress has less left than a whole one on average
    assert np.mean(b) < loadgen.mean_lengths(mix)["output_len"]
