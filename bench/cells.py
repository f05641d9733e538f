"""Finds a cell's pieces by name: its entry in ``BENCHMARK.json``, its
configuration file, its traffic mix, its metrics and their readers.

A later cell, mix or per-layer metric is added as files and entries
alone: ``configs/<config>.json``, ``traffic/<mix>.json`` and
``metrics/<metric>.py`` are looked up by the names ``BENCHMARK.json``
gives, ``families/<model_type>.py`` and ``reference/<reference>.py`` by
the configuration's keys.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from dataclasses import dataclass
from typing import Callable, List

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
# keys of a configuration file that the harness reads besides the
# model's published ``config.json`` keys, which its family checks
HARNESS_KEYS = ("source", "deployment", "reduced", "published", "assumed",
                "reference", "engine", "check")


@dataclass
class Cell:
    name: str
    chips: int
    config: dict           # the configuration file as it is run
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def engine(self) -> dict:
        return self.config["engine"]


def _read(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _for_cell(entries: List[dict], cell: str, e2e: List[str]) -> List[dict]:
    """The metrics a cell reports: those that list it, and those without a
    ``workloads`` key that move (or are) an end-to-end metric it reports."""
    out = []
    for e in entries:
        if "workloads" in e:
            if cell in e["workloads"]:
                out.append(e)
        elif e.get("moves", e["name"]) in e2e:
            out.append(e)
    return out


def load(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = _read(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read(root / configs[w["config"]]["file"])
    family(config).validate(config, HARNESS_KEYS)
    e2e = [e for e in bench["end_to_end"]
           if "workloads" not in e or name in e["workloads"]]
    e2e_names = [e["name"] for e in e2e]
    return Cell(name=name, chips=w["chips"], config=config,
                traffic=_read(root / "bench" / "traffic"
                              / f"{w['traffic']}.json"),
                end_to_end=e2e,
                per_layer=_for_cell(bench["per_layer"], name, e2e_names))


def _load(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, root: pathlib.Path = ROOT) -> Callable:
    """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
    return _load(root / "bench" / "metrics" / f"{metric}.py",
                 "bench_metric_" + metric.replace(".", "_")).read


def reference(config: dict):
    """The plain reference module the configuration names."""
    return _load(BENCH / "reference" / f"{config['reference']}.py",
                 "bench_reference_" + config["reference"])


def family(config: dict):
    """The module of the configuration's model family,
    ``families/<model_type>.py``: its mapping onto the program's
    ``ArchConfig`` and its operation and byte counts."""
    path = BENCH / "families" / f"{config['model_type']}.py"
    if not path.exists():
        raise KeyError(f"no family module for model_type "
                       f"{config['model_type']!r} ({path.name})")
    return _load(path, "bench_family_" + config["model_type"])
