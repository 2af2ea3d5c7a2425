"""CPU runs of the harness at a tiny size: the result line's schema, the
refusal without a TPU, and a cell added by data files alone."""
import json

import pytest

from chipbench.tests import tiny

DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("checkout"))


@pytest.fixture(scope="module")
def clean(root):
    return tiny.run(root)


def test_result_line_schema(clean):
    rc, lines, err = clean
    assert rc == 0
    res = json.loads(lines[-1])
    assert list(res)[-1] == "compared"
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(res)
    assert DEVICE_KEYS <= set(res["device"])
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    names = {"output_tok_s", "itl_p95_ms", "ttft_p90_ms", "setup_s"}
    assert set(res["metrics"]) == names
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(res["compared"]) == {"served_logit_gap_p50",
                                    "served_logit_gap_p90"}
    for cmp_ in res["compared"].values():
        assert set(cmp_) == {"value", "limit"}
    # the compared numbers close standard error
    assert err.strip().splitlines()[-1].startswith(
        "compared: served_logit_gap_p90")


def test_sound_run_is_correct(clean):
    """The engine's served tokens match the reference at the tiny size:
    the served-token logit gap stays under the tiny limit."""
    rc, lines, _ = clean
    res = json.loads(lines[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert 0 <= res["compared"]["served_logit_gap_p90"]["value"] < 0.01


def test_refuses_without_a_tpu(root):
    rc, lines, err = tiny.run(root, require_chip=True)
    assert rc != 0 and lines == []
    assert "needs 1 TPU" in err


def test_new_traffic_file_and_cell_run_through_dispatch(tmp_path):
    """A later change adds a cell by adding a traffic file and an entry
    in BENCHMARK.json; the harness finds both by name."""
    root = tiny.make_root(tmp_path, mix="tiny-burst", cell="tiny.burst")
    rc, lines, _ = tiny.run(root, cell="tiny.burst", seconds=1.0)
    assert rc == 0
    res = json.loads(lines[-1])
    assert res["correct"] is True
    assert "output_tok_s" in res["metrics"]


def test_traced_run_reports_its_program_metrics(root):
    """``--trace 1``: the per-layer metrics replace the end-to-end ones.
    The CPU has no device plane, so the trace's metrics are left out and
    those read from the engine's counters and spans remain."""
    rc, lines, _ = tiny.run(root, trace=1)
    assert rc == 0
    res = json.loads(lines[-1])
    assert res["correct"] is True
    assert {"batch_occupancy", "queue_wait_p50_ms",
            "kv_saved_fraction"} <= set(res["metrics"])
    assert "output_tok_s" not in res["metrics"]
    assert 0 < res["metrics"]["batch_occupancy"]["value"] <= 100
    assert "busy_s" not in res["device"] and "breakdown" not in res


def test_compared_numbers_are_percentiles_of_the_gaps():
    from chipbench import check

    gaps = [0.0] * 6 + [0.1, 0.2, 0.4, 2.0]
    got = check.compared(gaps, {"served_logit_gap_p50": 0.2,
                                "served_logit_gap_p90": 0.3})
    assert got == {"served_logit_gap_p50": {"value": 0.0, "limit": 0.2},
                   "served_logit_gap_p90": {"value": pytest.approx(0.56),
                                            "limit": 0.3}}
    assert check.compared(None, {"served_logit_gap_p90": 1})[
        "served_logit_gap_p90"] == {"value": None, "limit": 1.0}
