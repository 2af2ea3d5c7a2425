"""What a cell is made of, found by name: the cell's entry in
``BENCHMARK.json``, its configuration file, the architecture family that
file names (``families/``) and its traffic mix file."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re
import sys
import types

from chipbench import loadgen

# Keys of a configuration file that must equal the built ModelConfig
# (the file holds the configuration as it is run), whatever its family;
# a family adds its own (``hf_keys``).
HF_TO_REPRO = {
    "hidden_size": "d_model",
    "num_hidden_layers": "num_layers",
    "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}
FAMILY_NAME = re.compile(r"^[A-Za-z0-9_]+$")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict          # the configuration file
    family: types.ModuleType  # the architecture family it names
    traffic_name: str
    mix: dict             # the traffic mix file


def load_module(path: pathlib.Path, name: str) -> types.ModuleType:
    """The module in the file ``path``, imported as ``name``."""
    sp = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(sp)
    sys.modules[name] = mod
    sp.loader.exec_module(mod)
    return mod


def load_family(root: pathlib.Path, name) -> types.ModuleType:
    """The architecture family ``chipbench/families/<name>.py`` (its
    interface: ``chipbench/families/__init__.py``).  A name that no file
    answers stops the run, naming the families there are."""
    where = root / "chipbench" / "families"
    path = where / f"{name}.py"
    if not (isinstance(name, str) and FAMILY_NAME.match(name)
            and path.is_file()):
        have = sorted(p.stem for p in where.glob("*.py")
                      if not p.stem.startswith("_"))
        raise SystemExit(f"the configuration names family {name!r}: no "
                         f"such file under chipbench/families/; the "
                         f"families there are {have}")
    return load_module(path, f"chipbench_family_{name}")


def load_cell(root: pathlib.Path, workload: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have "
                         f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    c = configs[w["config"]]
    conf = json.loads((root / c["file"]).read_text())
    return Cell(
        name=workload, chips=int(w["chips"]), config_name=c["name"],
        config=conf, family=load_family(root, conf.get("family")),
        traffic_name=w["traffic"],
        mix=loadgen.load_mix(root / "chipbench" / "traffic"
                             / f"{w['traffic']}.json"))


def model_config(conf: dict, family: types.ModuleType):
    """The registry architecture with the file's overrides applied,
    checked against the file's published-name keys: the common ones and
    the family's."""
    from repro.configs import get_config

    rp = conf["repro"]
    cfg = get_config(rp["arch"])
    if rp.get("smoke"):
        cfg = cfg.smoke()
    cfg = dataclasses.replace(cfg, **rp.get("overrides", {}))
    for hf, field in {**HF_TO_REPRO, **family.hf_keys}.items():
        if hf in conf and conf[hf] != getattr(cfg, field):
            raise ValueError(f"{conf['name']}: {hf}={conf[hf]!r} but the "
                             f"built config has {field}="
                             f"{getattr(cfg, field)!r}")
    return cfg


def weights_key(seed: int):
    """The PRNG key the weights are drawn from; ``seed`` may exceed 32
    bits."""
    import jax

    key = jax.random.PRNGKey(seed % (1 << 31))
    return jax.random.fold_in(key, seed >> 31)
