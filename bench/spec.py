"""Find everything a cell needs by name.

- ``BENCHMARK.json`` at the root names the cells, configurations and
  metrics;
- a configuration is the file its entry names, a traffic mix is
  ``traffic/<name>.json``, a cell's correctness limits are
  ``limits/<cell>.json``, a per-layer metric's reader is
  ``metrics/<name>.py``, a model family's plain reference is
  ``reference/<family>.py`` and its hand-over to the program
  ``adapters/<family>.py`` (all under this directory).

Adding any of them is adding a file: nothing here lists them.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _named(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}; known: "
                   f"{[e['name'] for e in entries]}")


def workload(bench: dict, name: str) -> dict:
    return _named(bench["workloads"], name, "workload")


def config(bench: dict, name: str, root: Path = ROOT) -> dict:
    """The configuration file of ``name``, as JSON."""
    entry = _named(bench["configs"], name, "configuration")
    data = json.loads((Path(root) / entry["file"]).read_text())
    data.setdefault("name", name)
    return data


def limits(cell: str, bench_dir: Path = BENCH) -> dict:
    return json.loads((Path(bench_dir) / "limits" / f"{cell}.json")
                      .read_text())


def metrics_of(bench: dict, cell: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports: those
    that list it under ``workloads``, and those that list no cells."""
    return [m for m in bench[kind]
            if cell in m.get("workloads", [cell])]


def _module(path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_CACHE: Dict[Path, ModuleType] = {}


def module(kind: str, name: str, bench_dir: Path = BENCH) -> ModuleType:
    """``<bench_dir>/<kind>/<name>.py``, loaded once."""
    path = (Path(bench_dir) / kind / f"{name}.py").resolve()
    if not path.is_file():
        raise KeyError(f"no {kind} file {path.name} under {bench_dir}")
    if path not in _CACHE:
        _CACHE[path] = _module(path)
    return _CACHE[path]


def reader(metric: str, bench_dir: Path = BENCH):
    """The ``read(run)`` function of a per-layer metric."""
    return module("metrics", metric, bench_dir).read
