"""The benchmark's layout rules in tier-1 (PR 39; the originals stay under
``benchmarks/tests/``, run by hand, and are the benchmark's): the four
layout tests of ``test_benchmark_json.py`` (the per-layer list's room and
cells, no cell reading one thing under two names, a metric file's kinds, the
traced tree index of every traffic file), ``test_trace_index.py`` (a traced
run traces the same trees however long the window) and
``test_trace_reduce.py``'s tests of the idle gaps' labels and of the
asynchronous line, on the recorded fixture."""
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
HERE = os.path.join(BENCH, "tests")
sys.path[:0] = [BENCH, ROOT]

import gbdt_job  # noqa: E402
import trace_reduce as tr  # noqa: E402

B = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
FIXTURE = os.path.join(HERE, "fixtures", "tiny.xplane.pb.gz")


# ---- benchmarks/tests/test_benchmark_json.py: the layout -------------------

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
    files = (glob.glob(os.path.join(BENCH, "traffic", "*.json"))
             + glob.glob(os.path.join(BENCH, "tests", "traffic", "*.json")))
    assert len(files) >= 8
    for path in files:
        wl = json.load(open(path))
        if "trees_per_chunk" in wl:
            warmup = unit = int(wl["trees_per_chunk"])
        else:
            warmup, unit = int(wl["warmup_iters"]), int(wl["trace_units"])
        first = gbdt_job.trace_first_tree(wl, warmup, unit)
        assert first is not None and first > warmup, path


# ---- benchmarks/tests/test_trace_index.py ---------------------------------

def rehearse(cell, seconds, trace, tmp_path, rows=4096):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--cells-dir", os.path.join(HERE, "cells"), "--seed", "2147483659",
         "--seconds", str(seconds), "--trace", str(trace), "--rehearse-rows",
         str(rows)],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    window, = [ln for ln in done.stdout.splitlines()
               if ln.startswith("window ")]
    return window, json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,traced,units", [
    ("tiny_trace", "traced trees 6-7", 2 + 1),            # chunks of 2
    ("higgs1m_train_api", "traced trees 32-47", 16 + 16)])  # iterations
def test_two_window_lengths_trace_the_same_trees(cell, traced, units,
                                                 tmp_path):
    short, last_short = rehearse(cell, 0.01, 1, tmp_path)
    long_, last_long = rehearse(cell, 4, 1, tmp_path)
    assert traced in short and traced in long_
    # the untraced stretch is as long in both: the clock does not end it
    assert last_short["attempted"] == last_long["attempted"] == units


def test_an_untraced_window_goes_by_the_clock(tmp_path):
    short, _ = rehearse("tiny_trace", 0.01, 0, tmp_path)
    long_, _ = rehearse("tiny_trace", 3, 0, tmp_path)
    assert "traced trees" not in short + long_
    chunks = [int(re.search(r": (\d+) chunks", w).group(1))
              for w in (short, long_)]
    assert chunks[0] == 1 and chunks[1] > 3


@pytest.mark.parametrize("wl,warmup,unit", [
    ({"trace_first_tree": 60}, 8, 8), ({"trace_first_tree": 8}, 8, 8),
    ({"trace_first_tree": 46}, 4, 4), ({"trace_first_tree": 0}, 4, 4)])
def test_an_index_off_the_units_is_an_error(wl, warmup, unit):
    with pytest.raises(ValueError, match="whole number"):
        gbdt_job.trace_first_tree(wl, warmup, unit)


def test_a_kind_refuses_such_a_traffic_file_before_any_data(monkeypatch):
    from kinds import train_api, train_chunks
    monkeypatch.setattr(gbdt_job, "make_data", lambda *a, **k: pytest.fail(
        "data was made under a traffic file that cannot be traced"))
    with pytest.raises(ValueError):
        train_chunks.Job({}, {"trees_per_chunk": 8, "auc_trees": 16,
                              "trace_units": 1, "trace_first_tree": 60}, 1)
    with pytest.raises(ValueError):
        train_api.Job({}, {"warmup_iters": 4, "auc_trees": 16,
                           "trace_units": 4, "trace_first_tree": 46}, 1)
    assert gbdt_job.trace_first_tree({}, 8, 8) is None
    assert gbdt_job.trace_first_tree({"trace_first_tree": 64}, 8, 8) == 64
    assert gbdt_job.trace_first_tree({"trace_first_tree": 48}, 4, 4) == 48


# ---- benchmarks/tests/test_trace_reduce.py: gaps and the asynchronous line ----

@pytest.fixture(scope="module")
def recorded():
    return tr.load(FIXTURE)


def test_gaps_lead_with_the_programs_span():
    long = 2 * tr.SHORT_GAP_NS
    host = [("bench.unit", 0, 10 * long), ("gbdt.poll_stop", 20, 5 * long),
            ("np.asarray(jax.Array)", 25, 3 * long),
            ("PjitFunction(f)", 8 * long, 10)]
    idle = [(5, 8), (10, 10 + long), (8 * long + 2, 8 * long + 5)]
    names = {"gbdt.poll_stop", "bench.unit", "jax.lower"}
    assert tr.attribute_gaps(idle, host, names) == {
        "bench.unit: gaps under 10 us": 3,
        # the long gap: 10 ns before the program's span opens, 5 in it
        # before jax's, the rest under both
        "bench.unit: gaps of 10 us or more": 10,
        "gbdt.poll_stop: gaps of 10 us or more": 5,
        "gbdt.poll_stop > np.asarray(jax.Array): gaps of 10 us or more":
            long - 15,
        "bench.unit > PjitFunction(f): gaps under 10 us": 3}
    # no names given: the innermost span alone
    assert set(tr.attribute_gaps(idle, host)) == {
        "bench.unit: gaps under 10 us", "bench.unit: gaps of 10 us or more",
        "gbdt.poll_stop: gaps of 10 us or more",
        "np.asarray(jax.Array): gaps of 10 us or more",
        "PjitFunction(f): gaps under 10 us"}


def test_recorded_trace_has_its_asynchronous_line(recorded):
    (plane, later), = recorded["async"].items()
    assert plane == "/device:TPU:0" and len(later) == 218
    assert {tr.op_name(n).split(".")[0] for n, _, _ in later} == {
        "%copy-start", "%slice-start"}
    # every asynchronous op is also an event of the ops line, where it is
    # issued; on its own line it lasts until it is done
    issued = {tr.op_name(n) for n, _, _ in recorded["device"][plane]}
    assert {tr.op_name(n) for n, _, _ in later} <= issued


def test_reduce_labels_the_fixtures_gaps_with_the_programs_span():
    r = tr.reduce(FIXTURE, tr.UNIT_ANNOTATION, {"fused_train_chunk"})
    assert sum(r["idle"].values()) == pytest.approx(
        r["window_ns"] - r["busy_ns"], rel=1e-9)
    assert all(k.startswith(("bench.unit", "fused_train_chunk"))
               for k in r["idle"]), sorted(r["idle"])
    # the launch: the gap before the device starts lies under the chunk's
    # dispatch, and under jax's call inside it
    assert "fused_train_chunk > PjitFunction(converted): gaps of 10 us or " \
        "more" in r["idle"]
    assert "bench.unit: gaps of 10 us or more" in r["idle"]
