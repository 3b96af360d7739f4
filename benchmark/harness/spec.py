"""What ``BENCHMARK.json`` names, found by name on disk.

A cell names a configuration (its ``file``) and a traffic mix
(``traffic/<name>.json``); the mix's ``kind`` names the runner that
runs it (``kinds/<kind>.py``); each per-layer metric is read by
``metrics/<name>.py``. Adding a configuration, a mix or a metric is
adding files and entries: nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(Exception):
    pass


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as exc:
        raise SpecError(f"cannot read {path}: {exc}") from None


def load_module(path: str, name: str):
    """Import a file by path (names may hold dots and dashes)."""
    if not os.path.isfile(path):
        raise SpecError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Metric:
    name: str
    unit: str
    reader: object = None  # per-layer metrics: a module with read(facts)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file's contents
    mix: dict               # the traffic mix's parameters
    runner: object          # module with run(ctx)
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str, root: str = ROOT,
              bench_dir: str | None = None) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    bench_dir = bench_dir or os.path.join(root, "benchmark")
    spec = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec.get("workloads", [])}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in spec.get("configs", [])}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names unknown config "
                        f"{w['config']!r}")
    entry = configs[w["config"]]
    config = _load_json(os.path.join(root, entry["file"]))
    traffic = w["traffic"]
    mix = _load_json(os.path.join(bench_dir, "traffic", traffic + ".json"))
    kind = mix.get("kind")
    if not kind:
        raise SpecError(f"traffic {traffic!r} has no kind")
    runner = load_module(os.path.join(bench_dir, "kinds", kind + ".py"),
                         "kind_" + kind)
    e2e = [Metric(m["name"], m["unit"])
           for m in spec.get("end_to_end", []) if _applies(m, name)]
    layer = []
    for m in spec.get("per_layer", []):
        if not _applies(m, name):
            continue
        reader = load_module(
            os.path.join(bench_dir, "metrics", m["name"] + ".py"),
            "metric_" + m["name"])
        layer.append(Metric(m["name"], m["unit"], reader))
    return Cell(name, int(w["chips"]), config, mix, runner, e2e, layer)
