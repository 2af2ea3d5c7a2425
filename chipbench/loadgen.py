"""Closed-loop request generator, driven by a traffic mix file.

A mix (``chipbench/traffic/<name>.json``) gives the client count and the
prompt and output length ranges.  The lengths are ``pool`` values
stratified over the log-uniform distribution (one at each quantile
(i + 0.5) / pool), in an order fixed for the mix and not drawn from the
seed: a window serves a few tens of requests, and a seed that drew its
own order would change how much work that window holds (by ~20% in
tokens per second on the chip).  The seed draws the prompt token ids,
uniform over the vocabulary.  A request's budget is its output length;
there is no stop token.  A client's first request takes its budget from
``residual_budgets``: what remains of the requests in progress when a
closed loop runs steadily, so a run can open its window without waiting
for every slot's first request to end.
"""
from __future__ import annotations

import json
import math
import pathlib
from typing import List, Tuple

import numpy as np

REQUIRED = ("clients", "prompt_len", "output_len", "pool")


def load_mix(path) -> dict:
    mix = json.loads(pathlib.Path(path).read_text())
    missing = [k for k in REQUIRED if k not in mix]
    if missing:
        raise ValueError(f"{path}: traffic mix lacks {missing}")
    for key in ("prompt_len", "output_len"):
        lo, hi = mix[key]
        if not 1 <= lo <= hi:
            raise ValueError(f"{path}: bad {key} range {mix[key]}")
    return mix


def max_len(mix: dict) -> int:
    """Longest prompt plus longest output: the engine's ``max_len``."""
    return mix["prompt_len"][1] + mix["output_len"][1]


def mean_lengths(mix: dict) -> dict:
    """Mean prompt and output length of the mix's pool."""
    return {k: float(stratified_lengths(*mix[k], mix["pool"]).mean())
            for k in ("prompt_len", "output_len")}


def stratified_lengths(lo: int, hi: int, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n of log-uniform [lo, hi]."""
    q = (np.arange(n) + 0.5) / n
    x = np.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def residual_budgets(mix: dict) -> List[int]:
    """One first budget per client: the quantiles (c + 0.5) / clients of
    the tokens left in a request in progress at a random instant of a
    steady closed loop, where a request is in progress for a time in
    proportion to its output length L and has 1..L tokens left alike.
    Its distribution is F(r) = sum(min(r, L)) / sum(L) over the pool."""
    lens = stratified_lengths(*mix["output_len"], mix["pool"])
    r = np.arange(1, int(lens.max()) + 1)
    cdf = np.minimum(r[:, None], lens[None, :]).sum(axis=1) / lens.sum()
    q = (np.arange(mix["clients"]) + 0.5) / mix["clients"]
    return [int(x) for x in r[np.searchsorted(cdf, q)]]


class RequestPool:
    """The ``i``-th request a run submits is ``pool[i % pool]``."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.vocab = vocab
        self.rng = np.random.default_rng([seed, 0])
        order = np.random.default_rng(0)
        n = mix["pool"]
        self.prompt_lens = order.permutation(
            stratified_lengths(*mix["prompt_len"], n))
        self.output_lens = order.permutation(
            stratified_lengths(*mix["output_len"], n))
        self.i = 0

    def next(self) -> Tuple[np.ndarray, int]:
        k = self.i % len(self.prompt_lens)
        self.i += 1
        prompt = self.rng.integers(0, self.vocab, int(self.prompt_lens[k]),
                                   dtype=np.int32)
        return prompt, int(self.output_lens[k])
