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


def spec_of(metric):
    return json.load(open(os.path.join(BENCH, "layer_metrics",
                                       metric["name"] + ".json")))


def kind_of(cell):
    return json.load(open(os.path.join(
        BENCH, "traffic", cell["traffic"] + ".json")))["kind"]


def test_the_per_layer_list_has_room_and_every_entry_its_cells():
    """At most the contract's 128 entries; every entry lists the cells that
    report it, all of them listed cells; a metric file that no entry names
    is a leftover."""
    cells = {w["name"] for w in B["workloads"]}
    assert len(B["per_layer"]) <= 128
    for m in B["per_layer"]:
        assert m.get("workloads"), m["name"]
        assert set(m["workloads"]) <= cells, m["name"]
        assert len(set(m["workloads"])) == len(m["workloads"]), m["name"]
    assert sorted(os.listdir(os.path.join(BENCH, "layer_metrics"))) == sorted(
        m["name"] + ".json" for m in B["per_layer"])


def test_no_cell_reads_one_thing_under_two_names():
    """Two entries with the same reader and arguments are copies: fine for
    cells apart (a cell's PR may add files and edit none), never for one
    cell."""
    seen = {}
    for m in B["per_layer"]:
        spec = spec_of(m)
        key = (spec["reader"], json.dumps(spec["args"], sort_keys=True))
        for other, cells in seen.get(key, ()):
            assert not set(cells) & set(m["workloads"]), (m["name"], other)
        seen.setdefault(key, []).append((m["name"], m["workloads"]))


def test_a_metrics_kinds_are_those_of_its_cells():
    """Every kind a metric file lists is the kind of a cell on the entry's
    list, or a kind no listed cell has (``train_api``: the probes under
    ``tests/cells``); every cell on the list has a kind the file lists, or
    the entry could never be reported there."""
    kinds = {w["name"]: kind_of(w) for w in B["workloads"]}
    for m in B["per_layer"]:
        spec = spec_of(m)
        of_cells = {kinds[c] for c in m["workloads"]}
        assert of_cells <= set(spec["kinds"]), m["name"]
        assert not (set(spec["kinds"]) - of_cells) & set(kinds.values()), \
            m["name"]


def test_every_traffic_mix_states_the_tree_it_is_traced_at():
    """``trace_first_tree`` is the warm-up plus whole units: the kind's own
    rule, asked of every traffic file here and under ``tests/``."""
    import glob

    import gbdt_job
    files = (glob.glob(os.path.join(BENCH, "traffic", "*.json"))
             + glob.glob(os.path.join(BENCH, "tests", "traffic", "*.json")))
    assert len(files) >= 8
    for path in files:
        wl = json.load(open(path))
        if "trees_per_chunk" in wl:
            warmup = unit = int(wl["trees_per_chunk"])
            # where a chunk mix ends its window at a tree, that tree obeys
            # the same rule
            end = gbdt_job.window_end_tree(wl, warmup, unit)
            assert end is None or end > warmup, path
        else:
            warmup, unit = int(wl["warmup_iters"]), int(wl["trace_units"])
            # the iteration kinds' windows go by the clock
            assert "window_end_tree" not in wl, path
        first = gbdt_job.trace_first_tree(wl, warmup, unit)
        assert first is not None and first > warmup, path
