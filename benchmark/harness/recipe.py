"""Cell, configuration, traffic and metric files, found by name.

Everything that belongs to one cell is data under ``benchmark/``: a
later PR adds files and a ``workloads`` entry and edits nothing. A
recipe pins only the knobs without which the cell does not fit or would
compile programs nobody serves; a pin the program no longer accepts is
dropped and named on an output line (ROADMAP D2/D4 delete such knobs in
PRs that may not edit these files).
"""

from __future__ import annotations

import dataclasses
import importlib
import inspect
import json
import os
import sys
from typing import Any, Callable, Dict, List, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(ROOT)


def log(msg: str) -> None:
    """Progress lines go to stderr; stdout carries the result line."""
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts: str) -> Dict[str, Any]:
    path = os.path.join(ROOT, *parts)
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> Dict[str, Any]:
    """The cell's ``workloads`` entry of BENCHMARK.json joined with its
    recipe, configuration and traffic files."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(
            f"benchmark: no workload {name!r} in BENCHMARK.json "
            f"(have {[w['name'] for w in bench['workloads']]})")
    config = load_json("configs", entry["config"] + ".json")
    traffic = load_json("traffic", entry["traffic"] + ".json")
    recipe = load_json("cells", name + ".json")
    return {"name": name, "chips": entry["chips"], "config": config,
            "traffic": traffic, "recipe": recipe, "bench": bench,
            "family": importlib.import_module(
                "benchmark.families." + config["family"]),
            "job": importlib.import_module(
                "benchmark.jobs." + traffic["kind"])}


def accepted(target: Callable, pins: Dict[str, Any], what: str
             ) -> Dict[str, Any]:
    """The pins ``target`` (a dataclass or a function) still takes; the
    others are dropped and named, not an error."""
    if dataclasses.is_dataclass(target):
        names = {f.name for f in dataclasses.fields(target)}
    else:
        names = set(inspect.signature(target).parameters)
    kept = {k: v for k, v in pins.items() if k in names}
    for k in pins:
        if k not in names:
            log(f"recipe: {what} no longer takes {k!r}; pin dropped")
    return kept


def layer_metric_specs(job_kind: str, chips: int) -> List[Dict[str, Any]]:
    """Every ``layer_metrics/*.json`` that applies to a job of this kind
    on this many chips — never a list of cell names, so a new cell
    inherits its kind's metrics."""
    out = []
    folder = os.path.join(ROOT, "layer_metrics")
    for fn in sorted(os.listdir(folder)):
        if not fn.endswith(".json"):
            continue
        spec = load_json("layer_metrics", fn)
        spec.setdefault("name", fn[:-5])
        if job_kind in spec["job_kinds"] and chips >= spec.get(
                "min_chips", 1):
            out.append(spec)
    return out


def reader_of(spec: Dict[str, Any]) -> Tuple[Callable, Dict[str, Any]]:
    """``(function, parameters)`` of a metric's reader:
    ``"reader": "module:function"`` under ``layer_metrics/readers/``."""
    mod, fn = spec["reader"].split(":")
    module = importlib.import_module("benchmark.layer_metrics.readers." + mod)
    return getattr(module, fn), spec.get("params", {})
