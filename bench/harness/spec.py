"""The benchmark's description: ``BENCHMARK.json`` at the checkout root, and
the files it names by convention.

A cell (``workloads`` entry) names a configuration and a traffic mix.  The
configuration is the ``file`` its entry gives, the traffic mix
``traffic/<traffic>.json``, and every metric a reader ``metrics/<name>.py``
-- all under ``bench/``.  A configuration file names, under ``"reference"``,
the module of its architecture (``architecture``): its weight tree, its
operation count, its attention windows and its plain forward pass.  Adding
a cell, a metric or an architecture therefore means adding files and
entries, never editing one.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

ROOT = Path(__file__).resolve().parents[2]


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file's contents
    traffic: dict         # the traffic file's contents
    end_to_end: list      # metric entries this cell reports with --trace 0
    per_layer: list       # ... and with --trace 1
    run_seconds: int
    limits: dict          # limits/<cell>.json: what ``correct`` compares


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration, traffic and metrics.
    Raises ``KeyError`` for an unknown cell and ``FileNotFoundError`` when a
    file it names is missing."""
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        run_seconds=int(spec["run_seconds"]),
        limits=load_json(root / "bench" / "limits" / f"{name}.json"),
    )


def import_file(path: Path, name: str) -> ModuleType:
    """The Python file ``path``, imported as a module called ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def architecture(m: dict) -> ModuleType:
    """The module that configuration ``m`` names under ``"reference"``, a
    path relative to the checkout root.  It provides ``shapes(m)`` (the
    weight tree), ``matmul_params(m)`` (weights one token multiplies
    through), ``attention_windows(m)`` (one entry per attention layer, its
    window or None), and the plain forward pass in ``embed``, ``block`` and
    ``head``; optionally ``OWN_KEYS``, configuration keys it reads itself and
    the program's ``ModelConfig`` does not have."""
    if "reference" not in m:
        raise KeyError(f"configuration {m.get('name')!r} names no "
                       f'"reference" module for its architecture')
    return _architecture(m["reference"])


@functools.cache
def _architecture(rel: str) -> ModuleType:
    return import_file(ROOT / rel, "bench_arch_" + re.sub(r"\W", "_", rel))
