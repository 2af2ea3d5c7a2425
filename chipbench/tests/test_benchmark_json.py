"""BENCHMARK.json keeps the benchmark's contract, and every name in it
resolves to a file of its own."""
import json
import math
import pathlib
import re

import pytest

from chipbench import loadgen, spec

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_a_full_check_fits_its_time():
    runs = 2 + 14 * 24
    total = runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def test_names_units_and_sources():
    names = [c["name"] for c in BENCH["configs"]]
    names += [w["name"] for w in BENCH["workloads"]]
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for n in names + [m["name"] for m in metrics]:
        assert NAME.match(n), n
    assert len(set(c["name"] for c in BENCH["configs"])) == len(
        BENCH["configs"])
    assert len(set(w["name"] for w in BENCH["workloads"])) == len(
        BENCH["workloads"])
    assert len(set(m["name"] for m in metrics)) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


def test_per_layer_metrics_move_an_end_to_end_metric_of_their_cells():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").exists()
        for c in m.get("workloads", cells):
            assert c in cells
            moved = e2e[m["moves"]]
            assert c in moved.get("workloads", cells), (m["name"], c)
        if m["unit"] == "%" and ("roofline" in m["name"]
                                 or "mfu" in m["name"]):
            assert m["better"] == "higher"


def test_every_cell_reports_setup_another_e2e_and_a_per_layer_metric():
    for w in BENCH["workloads"]:
        e2e = [m["name"] for m in BENCH["end_to_end"]
               if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(w["name"] in m.get("workloads", [w["name"]])
                   for m in BENCH["per_layer"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_files_resolve(w):
    cell = spec.load_cell(ROOT, w["name"])
    cfg = spec.model_config(cell.config, cell.family)
    conf = {c["name"]: c for c in BENCH["configs"]}[w["config"]]
    assert cell.config["name"] == conf["name"]
    assert cell.config["source"] == conf["source"]
    assert cell.config["reduced"] == conf["reduced"]
    assert cfg.num_layers == cell.config["num_hidden_layers"]
    assert cell.mix["clients"] >= 1 and loadgen.max_len(cell.mix) > 0
    limits = cell.config["correct_limit"]
    assert "served_logit_gap_p90" in limits
    for name, limit in limits.items():
        assert name.startswith("served_logit_gap_p")
        assert math.isfinite(limit) and limit > 0
