"""A checkout-shaped directory holding one tiny cell, for CPU runs of
the harness: BENCHMARK.json with the repository's metrics, the tiny
configuration and traffic mix of ``data/``, the architecture families and
the metric readers."""
import contextlib
import io
import json
import pathlib
import shutil

REPO = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
CELL = "tiny.chat"


def make_root(tmp: pathlib.Path, mix: str = "tiny-chat",
              cell: str = CELL) -> pathlib.Path:
    (tmp / "chipbench" / "configs").mkdir(parents=True, exist_ok=True)
    (tmp / "chipbench" / "traffic").mkdir(parents=True, exist_ok=True)
    shutil.copy(DATA / "tiny.json", tmp / "chipbench/configs/tiny.json")
    shutil.copy(DATA / f"{mix}.json", tmp / f"chipbench/traffic/{mix}.json")
    for part in ("metrics", "families"):
        if not (tmp / "chipbench" / part).exists():
            shutil.copytree(REPO / "chipbench" / part,
                            tmp / "chipbench" / part,
                            ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "chipbench/configs/tiny.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": cell, "config": "tiny", "traffic": mix,
                           "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [cell]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def run(root: pathlib.Path, *args, cell: str = CELL, seed: int = 2**31 + 9,
        seconds: float = 2.0, trace: int = 0, require_chip: bool = False):
    """(exit code, stdout lines, stderr text) of one harness run."""
    from chipbench import run as harness

    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = harness.main(["--workload", cell, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace),
                           *args], require_chip=require_chip, root=root)
    return rc, out.getvalue().splitlines(), err.getvalue()
