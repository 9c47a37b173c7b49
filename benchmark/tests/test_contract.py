"""BENCHMARK.json against the files it names: every cell, configuration,
traffic mix and per-layer metric is found by name, and the lists of
cells a metric exists in follow from the metric files' job kinds."""

import json
import os
import re

import pytest

from benchmark.harness import recipe

REPO = recipe.REPO
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_names_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 65536
    assert 1 <= bench["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(len(x["why"]) <= 200
               for k in ("configs", "workloads") for x in bench[k])
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    assert all(m["bound"] <= 0.1 for m in bench["end_to_end"])
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])


def test_every_cell_loads_by_name(bench):
    used = set()
    for w in bench["workloads"]:
        cell = recipe.load_cell(w["name"])
        used.add(w["config"])
        assert cell["config"]["family"] == "gpt2"
        assert hasattr(cell["job"], "Job")
    for c in bench["configs"]:
        assert c["name"] in used
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        # no width is ever reduced
        assert not any(k.endswith(("_dim", "_rank")) or k in (
            "n_embd", "n_head", "n_inner") for k in c["reduced"])


def test_metrics_follow_from_the_metric_files(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    listed = {m["name"]: m for m in bench["per_layer"]}
    cells = {w["name"]: recipe.load_cell(w["name"])
             for w in bench["workloads"]}
    derived = {}
    for name, cell in cells.items():
        for spec in recipe.layer_metric_specs(cell["traffic"]["kind"],
                                              cell["chips"]):
            derived.setdefault(spec["name"], []).append(name)
            recipe.reader_of(spec)      # the reader it names exists
    assert set(derived) == set(listed)
    for name, m in listed.items():
        spec = recipe.load_json("layer_metrics", name + ".json")
        for key in ("unit", "better", "source", "layer", "moves"):
            assert m[key] == spec[key], (name, key)
        want = derived[name]
        assert m.get("workloads", list(cells)) == want, name
        # a per-layer metric is reported only where the metric it
        # moves is
        moved = e2e[m["moves"]]
        assert set(want) <= set(moved.get("workloads", list(cells))), name
    for name, cell in cells.items():
        mine = [m for m in bench["end_to_end"]
                if name in m.get("workloads", [name])]
        assert len(mine) >= 2, name
