"""``BENCHMARK.json`` and the files it names, found by name.

A cell (an entry of ``workloads``) names a configuration and a traffic mix.
The configuration's file is the path ``BENCHMARK.json`` gives; the traffic
mix is ``traffic/<name>.json``; each per-layer metric is read by
``metrics/<name>.py``'s ``read(window)``; a configuration's plain reference
is ``references/<reference>.py``. Adding a cell, a mix or a metric adds
files and entries and edits none.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict               # the configuration file's contents
    traffic_name: str
    traffic: dict              # the traffic file's contents
    end_to_end: list = field(default_factory=list)   # metric entries
    per_layer: list = field(default_factory=list)


def load_benchmark(root: Path = ROOT) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def find_cell(name: str, root: Path = ROOT, bench_dir: Path = BENCH_DIR,
              bench: dict | None = None) -> Cell:
    bench = bench if bench is not None else load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(has {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(Path(root) / configs[w["config"]]["file"]) as f:
        config = json.load(f)
    with open(Path(bench_dir) / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic_name=w["traffic"], traffic=traffic,
                end_to_end=list(bench["end_to_end"]),
                per_layer=list(bench["per_layer"]))


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: Path = BENCH_DIR):
    """``metrics/<name>.py``'s ``read``: window -> number, or None when the
    window holds nothing for it to read."""
    path = Path(bench_dir) / "metrics" / f"{name}.py"
    return _load_module(path, f"chip_bench_metric_{name}").read


def reference(name: str, bench_dir: Path = BENCH_DIR):
    """``references/<name>.py``: a configuration's plain float32 model."""
    path = Path(bench_dir) / "references" / f"{name}.py"
    return _load_module(path, f"chip_bench_reference_{name}")
