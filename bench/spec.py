"""Finds a cell's pieces by the names in ``BENCHMARK.json``.

A cell (``workloads`` entry) names a configuration, whose file is given in
``configs``, and a traffic mix, read from ``bench/traffic/<traffic>.json``.
Its metrics are the ``end_to_end`` and ``per_layer`` entries that list it
under ``workloads`` or list no cells; each is read by
``bench/e2e/<name>.py`` or ``bench/metrics/<name>.py``.  A configuration
names its architecture by the published ``model_type``, served as
``bench/archs/<model_type>.py`` and checked against
``bench/reference/<model_type>.py``, and its execution family by
``family``, set up by ``bench/families/<family>.py``.  Adding a cell, mix,
configuration, architecture or metric adds files and entries; no code
here changes.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict  # the configuration file, with its "name"
    traffic: dict  # the traffic file, with its "name"
    end_to_end: list  # BENCHMARK.json entries
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> Cell:
    bm = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    entry = {c["name"]: c for c in bm["configs"]}[w["config"]]
    config = dict(load_json(os.path.join(root, entry["file"])), name=entry["name"])
    arch(config)  # a missing architecture or reference ends the run here,
    reference(config)  # before any phase
    traffic = dict(
        load_json(os.path.join(root, "bench", "traffic", w["traffic"] + ".json")),
        name=w["traffic"],
    )
    return Cell(
        name=name,
        chips=w["chips"],
        config=config,
        traffic=traffic,
        end_to_end=[m for m in bm["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bm["per_layer"] if _applies(m, name)],
    )


def reader(kind: str, name: str):
    """``read`` of ``bench/<kind>/<name>.py`` (names may hold dots)."""
    path = os.path.join(BENCH, kind, name + ".py")
    s = importlib.util.spec_from_file_location(f"bench.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod.read


def families() -> set:
    """The execution families: the modules of ``bench/families/``."""
    return {
        f[:-3] for f in os.listdir(os.path.join(BENCH, "families"))
        if f.endswith(".py") and f != "__init__.py"
    }


def _by_model_type(kind: str, c: dict):
    mt = c["model_type"]
    if mt in families():
        raise SystemExit(f"bench: model_type {mt!r} is the name of a family "
                         f"(bench/families/{mt}.py), not of an architecture")
    name = f"bench.{kind}.{mt}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise SystemExit(
            f"bench: no bench/{kind}/{mt}.py for model_type {mt!r}") from None


def arch(c: dict):
    """The architecture module of configuration ``c``: ``program_config``,
    ``weight_layout``, ``linear_work`` (``bench/archs/__init__.py``)."""
    return _by_model_type("archs", c)


def reference(c: dict):
    """The reference module of configuration ``c``: ``hidden``,
    ``head_stats`` (``bench/reference/__init__.py``)."""
    return _by_model_type("reference", c)
