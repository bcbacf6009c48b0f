"""Find a cell's pieces by name.

``BENCHMARK.json`` at the checkout root lists the cells (``workloads``), the
model configurations and the metrics.  Everything that belongs to one of
them sits in a file of its own, found by its name:

* ``configs/<config>.json``   the model, under the published config's keys;
* ``traffic/<traffic>.json``  the traffic mix: loop kind and length laws;
* ``cells/<workload>.json``   what this cell fixes for its pair: engine
  settings, the offered rate or client count, the correctness limit;
* ``metrics/<metric>.py``     one reader per per-layer metric;
* ``archs/<model_type>.py``   one file per architecture, found by the
  configuration file's ``model_type``.

A later cell adds files and ``BENCHMARK.json`` entries; nothing here changes.

To add an architecture, write ``archs/<model_type>.py`` with
``model_config(config)`` (the program's ``ModelConfig``), ``weight_key(seed)``
and ``logits_at(config, seed, seqs, rows, pad_to, low)`` (the plain
reference, which imports nothing of the program, its weight rule in its
docstring), ``step_counts(config, slots, emit)`` (operations and bytes of
one step, as ``counts.py`` counts them), ``PARTS`` (device scope -> the
peak in ``peaks.py`` its operations are held to), ``step_parts(config,
slots, emit)`` (``{scope: (operations, bytes)}`` for each of ``PARTS``,
their operations summing to the step's) and ``POOL`` (the scopes the KV
pool is read and written under).  A model type the program serves on the
path of another is a file that names it: ``SAME_AS = "<model_type>"``.
The trace is then split by those scopes, and a per-layer reader of a part
is one more file in ``metrics/``.
"""
from __future__ import annotations

import functools
import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
ARCHS = HERE / "archs"


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


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """``metrics/<name>.py``'s ``read(record)``."""
    return _load(HERE / "metrics" / f"{name}.py",
                 f"bench_metric_{name.replace('.', '_')}").read


@functools.cache
def _arch_file(path: Path):
    if not path.is_file():
        raise FileNotFoundError(f"no architecture file {path} for "
                                f"model_type {path.stem!r}")
    mod = _load(path, f"bench_arch_{path.stem}")
    same = getattr(mod, "SAME_AS", None)
    return _arch_file(path.with_name(f"{same}.py")) if same else mod


def arch(config: dict):
    """The architecture file of ``config["model_type"]``:
    ``archs/<model_type>.py``, or the file its ``SAME_AS`` names."""
    return _arch_file(ARCHS / f"{config['model_type']}.py")
