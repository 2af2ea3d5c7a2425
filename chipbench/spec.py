"""What a cell is made of, found by name: the cell's entry in
``BENCHMARK.json``, its configuration file and its traffic mix file."""
from __future__ import annotations

import dataclasses
import json
import pathlib

from chipbench import loadgen

# Keys of a configuration file that must equal the built ModelConfig
# (the file holds the configuration as it is run).
HF_TO_REPRO = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "num_hidden_layers": "num_layers",
    "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict          # the configuration file
    traffic_name: str
    mix: dict             # the traffic mix file


def load_cell(root: pathlib.Path, workload: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have "
                         f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    c = configs[w["config"]]
    return Cell(
        name=workload, chips=int(w["chips"]), config_name=c["name"],
        config=json.loads((root / c["file"]).read_text()),
        traffic_name=w["traffic"],
        mix=loadgen.load_mix(root / "chipbench" / "traffic"
                             / f"{w['traffic']}.json"))


def model_config(conf: dict):
    """The registry architecture with the file's overrides applied,
    checked against the file's published-name keys."""
    from repro.configs import get_config

    rp = conf["repro"]
    cfg = get_config(rp["arch"])
    if rp.get("smoke"):
        cfg = cfg.smoke()
    cfg = dataclasses.replace(cfg, **rp.get("overrides", {}))
    for hf, field in HF_TO_REPRO.items():
        if hf in conf and conf[hf] != getattr(cfg, field):
            raise ValueError(f"{conf['name']}: {hf}={conf[hf]!r} but the "
                             f"built config has {field}="
                             f"{getattr(cfg, field)!r}")
    if "head_dim" in conf and conf["head_dim"] != cfg.resolved_head_dim:
        raise ValueError(f"{conf['name']}: head_dim mismatch")
    return cfg


def weights_key(seed: int):
    """The PRNG key the weights are drawn from; ``seed`` may exceed 32
    bits."""
    import jax

    key = jax.random.PRNGKey(seed % (1 << 31))
    return jax.random.fold_in(key, seed >> 31)
