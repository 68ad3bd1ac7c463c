"""Finds what ``BENCHMARK.json`` names: a cell, its configuration file, its
traffic mix and the reader of each metric.

Every piece is found by name, so a new configuration, mix, cell or metric
is a new file plus an entry in ``BENCHMARK.json``:

  configuration  the ``file`` of its entry under ``configs``
  traffic mix    ``bench/traffic/<traffic>.json``
  metric         ``bench/metrics/<name>.py``, a module with ``read(run)``
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from types import ModuleType
from typing import Dict, List

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class SpecError(ValueError):
    """A name in ``BENCHMARK.json`` that resolves to nothing."""


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def _by_name(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"{what} {name!r} is not in BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    return _by_name(bench["workloads"], name, "workload")


def config(bench: dict, cell_entry: dict, root: pathlib.Path = ROOT) -> dict:
    entry = _by_name(bench["configs"], cell_entry["config"], "config")
    path = root / entry["file"]
    if not path.is_file():
        raise SpecError(f"config file {entry['file']} is missing")
    return json.loads(path.read_text())


def traffic(cell_entry: dict, root: pathlib.Path = ROOT) -> dict:
    path = root / "bench" / "traffic" / f"{cell_entry['traffic']}.json"
    if not path.is_file():
        raise SpecError(f"traffic mix {cell_entry['traffic']!r} has no "
                        f"file bench/traffic/{cell_entry['traffic']}.json")
    return json.loads(path.read_text())


def metrics(bench: dict, cell_name: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports: those
    without a ``workloads`` list, and those whose list names the cell."""
    return [m for m in bench[kind]
            if cell_name in m.get("workloads", [cell_name])]


_READERS: Dict[pathlib.Path, ModuleType] = {}


def reader(name: str, where: pathlib.Path = BENCH_DIR / "metrics"
           ) -> ModuleType:
    """The module ``bench/metrics/<name>.py``; it defines ``read(run)``."""
    path = where / f"{name}.py"
    if path not in _READERS:
        if not path.is_file():
            raise SpecError(f"metric {name!r} has no reader "
                            f"bench/metrics/{name}.py")
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{len(_READERS)}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _READERS[path] = mod
    return _READERS[path]
