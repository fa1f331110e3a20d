"""Find a cell's files by the names BENCHMARK.json gives.

A cell names a configuration and a traffic mix; a per-layer metric names
itself.  Each has a file of its own under ``perfbench/`` and each file
names the code that handles it (``builder``, ``driver``, ``reader``), so
a later PR adds files and edits none.
"""
from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def benchmark(root=ROOT) -> dict:
    return _load(os.path.join(root, "BENCHMARK.json"))


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"BENCHMARK.json has no workload {name!r}; it has "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(bench: dict, name: str, root=ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return _load(os.path.join(root, c["file"]))
    raise KeyError(f"BENCHMARK.json has no config {name!r}")


def traffic(name: str) -> dict:
    return _load(os.path.join(HERE, "traffic", name + ".json"))


def layer_metric(name: str) -> dict:
    return _load(os.path.join(HERE, "layer_metrics", name + ".json"))


def at_size(data: dict, rehearse: bool) -> dict:
    """A data file as run: its keys, with its tiny ``rehearse`` sizes
    laid over them for the CPU tests (groups merge one level deep)."""
    out = {k: v for k, v in data.items() if k != "rehearse"}
    if rehearse:
        for k, v in data.get("rehearse", {}).items():
            out[k] = {**out.get(k, {}), **v} if isinstance(v, dict) else v
    return out


def reports(metric: dict, cell_name: str) -> bool:
    """A metric without ``workloads`` is reported by every cell."""
    return "workloads" not in metric or cell_name in metric["workloads"]


def metrics_of(bench: dict, cell_name: str, group: str) -> list:
    return [m for m in bench[group] if reports(m, cell_name)]


def peaks(device_kind: str) -> dict:
    table = _load(os.path.join(HERE, "peaks.json"))["device_kinds"]
    if device_kind not in table:
        raise KeyError(f"perfbench/peaks.json has no device kind "
                       f"{device_kind!r} (it has {sorted(table)}); an "
                       f"unknown device is an error, not a default")
    return table[device_kind]


def module(kind: str, name: str):
    """``builders`` / ``drivers`` / ``readers`` + the name a data file
    gives."""
    return importlib.import_module(f"perfbench.{kind}.{name}")
