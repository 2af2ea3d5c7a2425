"""Whether what the timed path served is correct.

Once the window has closed, a sample of the requests it finished, drawn
from the seed and always holding the one with the most served tokens,
goes through the plain reference (``reference.py``): one forward over
each prompt followed by its served tokens.  At each served position the
number read is the gap by which the served token's reference logit lies
below the reference's best logit there (0 when the served token is the
reference's greedy choice).  The numbers compared are percentiles of
the gaps over the sample, ``served_logit_gap_p<q>``, one for each entry
of the configuration's ``correct_limit``, each held to its limit there
(PERF.md gives the readings they were set from, and why not the widest
gap).  The control reads the same gap for the token that the
lower-precision copy of the reference puts first at each position.
"""
from __future__ import annotations

from typing import List, Sequence

import numpy as np

PREFIX = "served_logit_gap_p"
SAMPLE_TOKENS = 256     # served tokens the sample reaches, at the least
SAMPLE_MAX = 12         # requests in the sample, at the most


def sample(reqs: Sequence, seed: int, t_open: float, t_close: float,
           tokens: int = SAMPLE_TOKENS, most: int = SAMPLE_MAX) -> List:
    """Requests that finished by their budget inside the window: the
    longest, then others in an order drawn from the seed, until the
    sample holds ``tokens`` served tokens or ``most`` requests."""
    done = sorted((r for r in reqs if r.t_done is not None
                   and t_open <= r.t_done <= t_close
                   and r.reason == "length"), key=lambda r: r.uid)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.tokens), -r.uid))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 2]).permutation(len(rest))
    out, n = [longest], len(longest.tokens)
    for i in order:
        if n >= tokens or len(out) >= most:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def sequences(reqs: Sequence):
    """(token sequences, rows): each prompt followed by all but the last
    served token, and the positions whose next-token logits judge the
    served tokens."""
    seqs, rows = [], []
    for r in reqs:
        toks = np.asarray(r.tokens, np.int32)
        seqs.append(np.concatenate([r.prompt, toks[:-1]]).astype(np.int32))
        rows.append(len(r.prompt) - 1 + np.arange(len(toks)))
    return seqs, rows


def gaps(ref_logits, picked) -> np.ndarray:
    """Per position: the reference's best logit minus its logit of the
    picked token."""
    import jax.numpy as jnp

    picked = jnp.asarray(picked, jnp.int32)
    at = jnp.take_along_axis(ref_logits, picked[:, None], axis=1)[:, 0]
    return np.asarray(ref_logits.max(axis=1) - at)


def served_gaps(result, reqs) -> np.ndarray:
    return np.concatenate([gaps(lg, r.tokens)
                           for lg, r in zip(result.logits, reqs)])


def compared(gaps, limits: dict) -> dict:
    """Each number named in ``limits`` (``served_logit_gap_p<q>``: the
    q-th percentile of ``gaps``) beside its limit; the values are None
    when there are no gaps."""
    return {name: {"value": (None if gaps is None else float(
                       np.percentile(gaps, float(name[len(PREFIX):])))),
                   "limit": float(limit)}
            for name, limit in limits.items()}


def entry_counts(result, reqs):
    """(KV entries the engine stored, entries the reference's gates
    store) for each request of the sample.  A difference is a net count
    of attention gates that rounding turned the other way."""
    return ([int(r.kv_stored) for r in reqs], list(result.entries))


def control_gaps(ref_result, ctrl_result) -> np.ndarray:
    """The gap of the token the control puts first, at each position."""
    return np.concatenate([gaps(lr, np.asarray(lc.argmax(axis=1)))
                           for lr, lc in zip(ref_result.logits,
                                             ctrl_result.logits)])
