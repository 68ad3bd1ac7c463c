"""Finds what ``BENCHMARK.json`` names: a cell, its configuration file and
the architecture that file names, its traffic mix and the reader of each
metric.

Every piece is found by name, so a new configuration, architecture, mix,
cell or metric is a new file plus an entry in ``BENCHMARK.json``:

  configuration  the ``file`` of its entry under ``configs``
  architecture   ``bench/arch/<arch>.py``, named by the configuration
                 file's ``"arch"`` key
  traffic mix    ``bench/traffic/<traffic>.json``
  metric         ``bench/metrics/<name>.py``, a module with ``read(run)``

An architecture module owns everything that depends on the model's
shape, and nothing else:

  sizes(name, model)         the file's ``model`` block read once: a
                             frozen, hashable value with at least
                             ``vocab`` and ``n_layers``
  program(m)                 the program's ``ModelConfig``
  layout(m)                  the weight layout (``weights.Layout``)
  layer_at(m, r)             (prefix, index): layer ``r``'s weights are
                             entry ``index`` of the stacked leaves under
                             ``prefix`` (a stage, or a position in one)
  layer(m, r, p, x, quant)   the float32 reference layer ``r``
  logits(m, top, h, quant)   the float32 reference head
  embed(m, top, toks)        the float32 reference embedding
  decode_work(m, ctxs)       (bytes, flops) of one decode execution over
                             rows at contexts ``ctxs``
  prefill_flops(m, S)        operations of a prefill of ``S`` tokens
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import sys
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


_MODULES: Dict[pathlib.Path, ModuleType] = {}


def _module(path: pathlib.Path) -> ModuleType:
    """The module at ``path``, loaded once (and registered, as a
    dataclass in it needs)."""
    if path not in _MODULES:
        spec = importlib.util.spec_from_file_location(
            f"bench_{path.parent.name}_{len(_MODULES)}", path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def reader(name: str, where: pathlib.Path = BENCH_DIR / "metrics"
           ) -> ModuleType:
    """The module ``bench/metrics/<name>.py``; it defines ``read(run)``."""
    path = where / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"metric {name!r} has no reader "
                        f"bench/metrics/{name}.py")
    return _module(path)


def arch(conf: dict, root: pathlib.Path = ROOT) -> ModuleType:
    """The module ``bench/arch/<arch>.py`` of the architecture a
    configuration file names: the checkout's at ``root``, else the
    harness's own (a test's checkout may hold only configuration files)."""
    if "arch" not in conf:
        raise SpecError("the configuration file names no \"arch\"")
    for where in (root / "bench" / "arch", BENCH_DIR / "arch"):
        path = where / f"{conf['arch']}.py"
        if path.is_file():
            return _module(path)
    raise SpecError(f"architecture {conf['arch']!r} has no module "
                    f"bench/arch/{conf['arch']}.py")
