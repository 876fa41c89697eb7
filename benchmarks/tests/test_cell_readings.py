"""What each cell reads, whatever its entries are called.

``fixtures/cell_readings.json`` lists every (reader, arguments) pair that the
per-layer list reads, with the metric's unit, direction, source, layer and
end-to-end metric, and the cells that report it: an entry reports a pair in a
cell when it lists the cell and its metric file lists the cell's kind, as
``run.layer_metrics`` asks. Every cell must still report each of its pairs,
under some entry, with the same description. "Still", not "only": a new
cell's entries (new files, suffixed copies) add pairs and rename none, and a
fold of copies into one shared entry keeps every pair of every cell. A
benchmark change that takes a metric away edits the fixture beside it."""
import json
import os

import pytest

from conftest import BENCH, ROOT

B = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
FIXTURE = os.path.join(BENCH, "tests", "fixtures", "cell_readings.json")
DESCRIBED = ("unit", "better", "source", "layer", "moves")


def kind_of(cell):
    return json.load(open(os.path.join(
        BENCH, "traffic", cell["traffic"] + ".json")))["kind"]


def reported(cell):
    """{(reader, arguments): [(entry name, description), ...]} of the
    entries the harness reads in this cell."""
    kind, found = kind_of(cell), {}
    for m in B["per_layer"]:
        spec = json.load(open(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".json")))
        if cell["name"] in m["workloads"] and kind in spec["kinds"]:
            key = (spec["reader"], json.dumps(spec["args"], sort_keys=True))
            found.setdefault(key, []).append(
                (m["name"], tuple(m[f] for f in DESCRIBED)))
    return found


def expected(cell_name):
    return {(r["reader"], json.dumps(r["args"], sort_keys=True)):
            tuple(r[f] for f in DESCRIBED)
            for r in json.load(open(FIXTURE)) if cell_name in r["cells"]}


# the fixture's cells; a later cell, which the fixture does not name, is held
# to nothing here, so its PR need not edit the fixture
CELLS = list(dict.fromkeys(c for r in json.load(open(FIXTURE))
                           for c in r["cells"]))


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_still_reads_what_it_read(cell):
    want = expected(cell)
    got = reported(next(w for w in B["workloads"] if w["name"] == cell))
    missing = sorted(key for key in want if key not in got)
    assert not missing, missing
    for key, described in want.items():
        assert [d for _, d in got[key]] == [described], (key, got[key])


def test_the_fixture_names_only_cells_of_the_benchmark():
    cells = {w["name"] for w in B["workloads"]}
    records = json.load(open(FIXTURE))
    keys = [(r["reader"], json.dumps(r["args"], sort_keys=True))
            for r in records]
    assert len(set(keys)) == len(keys)
    for r in records:
        assert r["cells"] and set(r["cells"]) <= cells, r
