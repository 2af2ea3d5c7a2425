"""The closed loop's pieces that need no engine: the warm-up ladder and
the window's numbers from the harness's own timestamps."""
import types

import numpy as np
import pytest

from chipbench import drive


def fake_engine(max_len=2560, steps=8):
    buckets = [16, 32, 64, 128, 256, 512, 1024, 2048, max_len]
    sched = types.SimpleNamespace(
        bucket_for=lambda n: min(b for b in buckets if b >= n))
    return types.SimpleNamespace(scheduler=sched, decode_steps=steps,
                                 max_len=max_len)


def test_warm_ladder_spans_each_bucket_and_the_longest_chain():
    mix = {"prompt_len": [512, 2048]}
    ladder = drive.warm_ladder(fake_engine(), mix, 100, 2**31 + 1)
    assert [len(p) for p, _ in ladder] == [512, 513, 1024, 1025, 2048, 2551]
    assert {n for _, n in ladder} == {9}
    assert all(p.dtype == np.int32 and p.max() < 100 for p, _ in ladder)
    again = drive.warm_ladder(fake_engine(), mix, 100, 2**31 + 1)
    assert all(np.array_equal(a, b) for (a, _), (b, _) in zip(ladder, again))


def req(uid, t_submit, times, prompt=4, budget=None, reason="length"):
    r = drive.Req(uid, 0, np.zeros(prompt, np.int32),
                  budget or len(times), t_submit)
    r.times = list(times)
    r.tokens = list(range(len(times)))
    r.t_done = times[-1] if reason else None
    r.reason = reason
    return r


def test_rate_is_taken_between_hand_out_instants():
    """Epochs hand out 8 tokens per slot at once: the tokens after the
    window's first hand-out, over the first to the last hand-out."""
    loop = types.SimpleNamespace(rejected=0, reqs={
        1: req(1, 0.0, [1.0] * 8 + [3.0] * 8 + [5.0] * 8),
        2: req(2, 0.5, [3.0] * 8 + [5.0] * 8 + [11.0] * 8),
    })
    st = drive.window_stats(loop, 2.0, 10.0)
    # inside [2, 10]: instants 3 and 5 with 16 tokens each
    assert st["output_tokens"] == 32
    assert st["output_tok_s"] == pytest.approx(16 / 2.0)
    assert st["handout_span_s"] == pytest.approx(2.0)


def test_window_tails_and_counts():
    loop = types.SimpleNamespace(rejected=1, reqs={
        1: req(1, 1.0, [2.0, 4.0, 5.0], prompt=10, budget=3),
        2: req(2, 3.0, [], prompt=20, budget=7, reason=None),
        3: req(3, 0.0, [0.5, 1.5], reason="deadline"),
    })
    st = drive.window_stats(loop, 0.8, 6.0)
    assert st["attempted"] == 2            # submitted inside the window
    assert st["failed"] == 1               # the rejected submission
    assert st["ttft_count"] == 2
    # request 1 waited 1 s; request 2 has no token yet: 3 s so far
    assert st["ttft_p50_ms"] == pytest.approx(2000.0)
    assert st["itl_count"] == 2            # 2->4 and 4->5
    assert st["mean_prompt"] == 15.0 and st["mean_output"] == 5.0
    assert st["finished"] == 2
