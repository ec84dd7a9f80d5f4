"""A cell's parts, found by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix.  Its configuration is
``bench/configs/<config>.json`` (the model's published ``config.json``
keys as run, plus its deployment settings), its mix is
``bench/traffic/<mix>.json`` (parameters of the one generator in
``harness/traffic.py``), and what belongs to the cell alone (its fixed
arrival rate and the output check's limit) is
``bench/cells/<cell>.json``.  A later cell adds files and entries; no
file here needs an edit.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

#: published config.json key -> the program's ModelConfig field
_WIDTHS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "hidden_act": "act",
}


def _load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    settings: dict
    end_to_end: List[dict] = field(default_factory=list)
    per_layer: List[dict] = field(default_factory=list)
    home: Path = BENCH      # where traffic/, cells/ and metrics/ live

    @property
    def deployment(self) -> dict:
        return self.config["deployment"]

    @property
    def rate_per_s(self) -> float:
        return float(self.settings["rate_per_s"])

    def limit(self, check: str) -> float:
        return float(self.settings["limits"][check])


def load_cell(name: str, bench: Path = BENCH,
              benchmark: Optional[dict] = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` (or of ``benchmark``),
    with its configuration, mix and settings read from their files."""
    if benchmark is None:
        benchmark = _load(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in benchmark["configs"]}
    config = _load(ROOT / configs[w["config"]]["file"])
    # an end-to-end metric with no list is reported in every cell
    e2e = [m for m in benchmark["end_to_end"]
           if name in m.get("workloads", [name])]
    per_layer = [m for m in benchmark["per_layer"]
                 if name in m["workloads"]]
    return Cell(name=name, chips=int(w["chips"]),
                config_name=w["config"], traffic_name=w["traffic"],
                config=config,
                traffic=_load(bench / "traffic" / f"{w['traffic']}.json"),
                settings=_load(bench / "cells" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer, home=bench)


def program_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file, built
    from the file's published keys.  Where the file names a registry
    entry, every field but the depth must equal that entry's (a
    mismatch is an error, never a silent substitution)."""
    from repro.configs.base import ModelConfig
    if config.get("attention_bias") or config.get("sliding_window") \
            or config.get("model_type") != "qwen3":
        raise ValueError("not a Qwen3 dense block (bias, sliding window "
                         "or model type)")
    fields = {fld: config[key] for key, fld in _WIDTHS.items()}
    cfg = ModelConfig(name=config.get("registry", "bench"), family="dense",
                      use_qk_norm=True, **fields)
    if "registry" in config:
        from repro.configs import get_config
        reg = get_config(config["registry"]).replace(
            num_layers=cfg.num_layers)
        if reg != cfg:
            raise ValueError(f"configuration file and the program's "
                             f"registry differ:\n{cfg}\nvs\n{reg}")
    return cfg


def peaks(kind: str, bench: Path = BENCH) -> Dict[str, float]:
    table = _load(bench / "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"device kind {kind!r} has no entry in "
                         f"bench/peaks.json (known: {sorted(table)}); "
                         f"add its published peaks before measuring it")
    return table[kind]
