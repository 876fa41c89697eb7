"""``BENCHMARK.json`` against the contract's limits on names and against the
files the harness finds by those names."""
import importlib
import json
import os
import re

from conftest import BENCH, ROOT

B = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_keys_names_and_units():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    metrics = B["end_to_end"] + B["per_layer"]
    names = ([m["name"] for m in metrics] + [c["name"] for c in B["configs"]]
             + [w["name"] for w in B["workloads"]]
             + [w["traffic"] for w in B["workloads"]]
             + [k for c in B["configs"] for k in c["reduced"]])
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    assert all(0 < m["bound"] <= 0.1 for m in B["end_to_end"])
    assert "setup_s" in {m["name"] for m in B["end_to_end"]}
    e2e = {m["name"] for m in B["end_to_end"]}
    assert all(m["moves"] in e2e for m in B["per_layer"])
    for text in ([w["why"] for w in B["workloads"]]
                 + [c["why"] for c in B["configs"]]
                 + [c["source"] for c in B["configs"]]
                 + [m["layer"] for m in B["per_layer"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    assert 4 * sum(w["chips"] == 4 for w in B["workloads"]) <= max(
        len(B["workloads"]), 4)


def test_every_name_has_its_file():
    used = {w["config"] for w in B["workloads"]}
    assert used == {c["name"] for c in B["configs"]}
    for c in B["configs"]:
        assert c["file"].startswith("benchmarks/")
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
    for w in B["workloads"]:
        wl = json.load(open(os.path.join(BENCH, "traffic",
                                         w["traffic"] + ".json")))
        importlib.import_module("kinds." + wl["kind"])
    for m in B["per_layer"]:
        spec = json.load(open(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".json")))
        assert (spec["layer"], spec["unit"], spec["moves"], spec["better"]) \
            == (m["layer"], m["unit"], m["moves"], m["better"])
        assert hasattr(importlib.import_module("readers." + spec["reader"]),
                       "read")
