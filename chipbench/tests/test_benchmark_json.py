"""BENCHMARK.json: names and units use only the allowed characters, and
every cell finds its files by name."""

import json
import os
import re

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
from chipbench.run import SOURCE_KINDS as KINDS  # noqa: E402


def load(*p):
    with open(os.path.join(*p)) as f:
        return json.load(f)


def test_names_units_and_files():
    b = load(REPO, "BENCHMARK.json")
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for c in b["configs"]:
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        assert os.path.exists(os.path.join(REPO, c["file"]))
        d = os.path.dirname(os.path.join(REPO, c["file"]))
        serve = load(d, "serve.json")
        assert serve["reduced"] == c["reduced"] and serve["source"] == c["source"]
        assert any(w["config"] == c["name"] for w in b["workloads"])
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(HERE, "traffic", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(HERE, "cells", w["name"] + ".json"))
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    assert "setup_s" in e2e and all(0.01 <= m["bound"] <= 0.1
                                    for m in b["end_to_end"])
    for m in b["per_layer"]:
        spec = load(HERE, "layer_metrics", m["name"] + ".json")
        assert KINDS[spec["source"]] == m["source"]
        assert (spec["layer"], spec["moves"], spec["unit"]) == (
            m["layer"], m["moves"], m["unit"])
        assert os.path.exists(os.path.join(HERE, "reducers",
                                           spec["reducer"] + ".py"))
        moved = e2e[m["moves"]]
        assert set(m.get("workloads", cells)) <= set(
            moved.get("workloads", cells))
    for cell in cells:  # setup_s + one more end-to-end + one per-layer
        assert sum(cell in m.get("workloads", cells)
                   for m in b["end_to_end"]) >= 2
        assert any(cell in m.get("workloads", cells) for m in b["per_layer"])
