"""``BENCHMARK.json`` and the files it names, resolved by name.

A cell (an entry of ``workloads``) names a configuration, whose entry in
``configs`` gives its file, and a traffic mix, read from
``traffic/<traffic>.json``.  Every metric, end to end or per layer, is
computed by its own reader ``metrics/<name>.py``; a cell reports the
metrics whose ``workloads`` list it, or all cells where a metric has no
such list.  Adding a cell, configuration, mix or metric is adding files
and entries: nothing here names one.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys

BENCH_DIR = pathlib.Path(__file__).resolve().parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list  # metric entries of BENCHMARK.json
    per_layer: list


def load(root: pathlib.Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(spec: dict, root: pathlib.Path, name: str,
         bench_dir: pathlib.Path = BENCH_DIR) -> Cell:
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = by_name[name]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise KeyError(f"workload {name!r} names no known config")
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, name)],
    )


def _load_module(path: pathlib.Path, label: str):
    if not path.is_file():
        raise FileNotFoundError(f"{label}: no file {path}")
    name = f"perfbench._{label}.{path.stem}"
    if name in sys.modules:
        return sys.modules[name]
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    sys.modules[name] = mod
    mod_spec.loader.exec_module(mod)
    return mod


def reader(metric: str, bench_dir: pathlib.Path = BENCH_DIR):
    """The reader of ``metric``: a module with ``read(record)``, which
    returns the metric's value or None where it finds nothing to read."""
    return _load_module(bench_dir / "metrics" / f"{metric}.py", "metric")


def named(kind: str, name: str, bench_dir: pathlib.Path = BENCH_DIR):
    """``inputs/<name>.py``, ``systems/<name>.py`` or
    ``references/<name>.py``."""
    return _load_module(bench_dir / kind / f"{name}.py", kind)
