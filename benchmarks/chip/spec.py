"""Find a cell's pieces by name.

``BENCHMARK.json`` at the checkout root lists the cells (``workloads``), the
model configurations and the metrics.  Everything that belongs to one of
them sits in a file of its own, found by its name:

* ``configs/<config>.json``   the model, under the published config's keys;
* ``traffic/<traffic>.json``  the traffic mix: loop kind and length laws;
* ``cells/<workload>.json``   what this cell fixes for its pair: engine
  settings, the offered rate or client count, the correctness limit;
* ``metrics/<metric>.py``     one reader per per-layer metric.

A later cell adds files and ``BENCHMARK.json`` entries; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict                    # configs/<config>.json
    traffic: dict                   # traffic/<traffic>.json
    settings: dict                  # cells/<workload>.json
    end_to_end: list = field(default_factory=list)   # metric entries
    per_layer: list = field(default_factory=list)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(workload: str, bench: dict | None = None) -> Cell:
    bench = load_benchmark() if bench is None else bench
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(known: {sorted(by_name)})")
    w = by_name[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    config = _json(ROOT / cfg_entry["file"])
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        traffic=_json(HERE / "traffic" / f"{w['traffic']}.json"),
        settings=_json(HERE / "cells" / f"{workload}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, workload)])


def metric_reader(name: str):
    """``metrics/<name>.py``'s ``read(record)``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# Published config.json keys -> the program's ModelConfig fields.
_ARCH_KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig

    if config["hidden_act"] != "silu":
        raise ValueError(f"{config['name']}: the program's dense MLP is "
                         f"gated SiLU, not {config['hidden_act']!r}")
    kw = {dst: config[src] for src, dst in _ARCH_KEYS.items()}
    return ModelConfig(name=config["name"], family="dense", mlp_gated=True,
                       ternary=True, **kw)
