"""An untraced window ends when the booster holds the traffic file's
``window_end_tree`` trees, the same trees on every seed and every commit, with
``gbdt_job.CEILING`` x ``--seconds`` as a ceiling that the run names when it
cuts; a mix that states no such tree goes by the clock.  The rule on the
index, the loop's test on a made-up clock, and rehearsals on the CPU."""
import json
import os
import re
import subprocess
import sys
import types

import pytest

from conftest import BENCH, HERE, ROOT

import gbdt_job

B = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def traffic_of(cell):
    return json.load(open(os.path.join(BENCH, "traffic",
                                       cell["traffic"] + ".json")))


@pytest.mark.parametrize("cell", [w["name"] for w in B["workloads"]])
def test_every_one_chip_chunk_cell_ends_its_window_at_tree_88(cell):
    """The one-chip chunk cells end their windows at tree 88; the four-chip
    cell keeps the clock (its rate spreads under 0.1% with it)."""
    w = next(w for w in B["workloads"] if w["name"] == cell)
    wl = traffic_of(w)
    if w["chips"] == 4:
        assert "window_end_tree" not in wl
        return
    assert wl["kind"].startswith("train_chunks")
    k = int(wl["trees_per_chunk"])
    end = gbdt_job.window_end_tree(wl, warmup=k, unit=k)
    assert end == 88 and end > gbdt_job.trace_first_tree(wl, k, k)


def test_the_other_mixes_state_no_window_end():
    for name in ("api_dp_iters", "chunks_k16"):
        wl = json.load(open(os.path.join(BENCH, "traffic", name + ".json")))
        assert "window_end_tree" not in wl, name
        assert gbdt_job.window_end_tree(wl, 4, 4) is None


@pytest.mark.parametrize("wl,warmup,unit", [
    ({"window_end_tree": 84}, 8, 8), ({"window_end_tree": 8}, 8, 8),
    ({"window_end_tree": 86}, 4, 4), ({"window_end_tree": 0}, 4, 4)])
def test_an_end_off_the_units_is_an_error(wl, warmup, unit):
    with pytest.raises(ValueError, match="whole number"):
        gbdt_job.window_end_tree(wl, warmup, unit)


def test_a_kind_refuses_such_a_traffic_file_before_any_data(monkeypatch):
    from kinds import train_chunks
    monkeypatch.setattr(gbdt_job, "make_data", lambda *a, **k: pytest.fail(
        "data was made under a traffic file whose window cannot end"))
    with pytest.raises(ValueError, match="window_end_tree"):
        train_chunks.Job({}, {"trees_per_chunk": 8, "auc_trees": 16,
                              "trace_units": 1, "window_end_tree": 84}, 1)
    assert gbdt_job.window_end_tree({"window_end_tree": 88}, 4, 4) == 88


@pytest.fixture
def at(monkeypatch):
    """``at(t)`` sets the harness's clock to ``t`` seconds after the window
    opened."""
    now = [0.0]
    monkeypatch.setattr(gbdt_job, "clock", lambda: now[0])

    def set_to(t):
        now[0] = t
    return set_to


def job(end, first=64):
    return types.SimpleNamespace(t_start=0.0, window_end_tree=end,
                                 trace_first_tree=first)


def a_clock_kind_job():
    """A job of a kind with no ``window_end_tree`` at all (``train_api``)."""
    return types.SimpleNamespace(t_start=0.0, trace_first_tree=64)


@pytest.mark.parametrize("trees,elapsed,goes_on", [
    (8, 0.0, True), (80, 19.9, True), (80, 25.0, True), (80, 59.9, True),
    (88, 10.0, False), (88, 61.0, False), (96, 5.0, False),
    (80, 60.0, False), (16, 75.0, False)])
def test_the_window_goes_on_to_its_tree_under_the_ceiling(at, capsys, trees,
                                                          elapsed, goes_on):
    at(elapsed)
    j = job(88)
    assert gbdt_job.untraced_goes_on(j, None, 20, trees) is goes_on
    said = capsys.readouterr().out
    # the run names a cut, and only a cut: a window that reached its tree
    # says nothing
    cut = trees < 88 and not goes_on
    assert ("window cut at tree %d" % trees in said) is cut
    assert bool(said) is cut
    assert getattr(j, "window_ended_at", None) == (
        None if goes_on else trees)
    assert gbdt_job.window_check(j) == ([] if goes_on else [
        ("window_reached_its_end", not cut, "the window ended at tree %d; "
         "the traffic file's window ends at tree 88" % trees)])


def test_a_mix_with_no_end_goes_by_the_clock(at, capsys):
    for make in (lambda: job(None), a_clock_kind_job):
        at(19.9)
        assert gbdt_job.untraced_goes_on(make(), None, 20, 10 ** 6)
        at(20.0)
        j = make()
        assert not gbdt_job.untraced_goes_on(j, None, 20, 8)
        assert gbdt_job.window_check(j) == []
    assert capsys.readouterr().out == ""


def test_a_traced_run_goes_to_its_first_traced_tree_whatever_the_clock(at):
    at(1e6)
    assert gbdt_job.untraced_goes_on(job(88), object(), 20, 56)
    at(0.0)
    j = job(88)
    assert not gbdt_job.untraced_goes_on(j, object(), 20, 64)
    assert gbdt_job.window_check(j) == []


def rehearse(seconds, tmp_path, trace=0):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "tiny_window_end", "--cells-dir", os.path.join(HERE, "cells"),
         "--seed", "2147483659", "--seconds", str(seconds), "--trace",
         str(trace), "--rehearse-rows", "4096"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.splitlines()
    window, = [ln for ln in lines if ln.startswith("window ")]
    cuts = [ln for ln in lines if ln.startswith("ceiling: ")]
    reached, = [ln for ln in lines if "window_reached_its_end" in ln]
    return window, cuts, reached, json.loads(lines[-1])


def test_a_rehearsal_ends_at_the_tree_or_at_the_ceiling(tmp_path):
    window, cuts, reached, last = rehearse(300, tmp_path)
    # trees 2-7: three chunks of 2 after the warm-up chunk, however long
    # --seconds is
    assert re.search(r": 3 chunks of 2 trees", window), window
    assert not cuts and last["attempted"] == 3 and last["failed"] == 0
    assert reached == ("ok  window_reached_its_end: the window ended at tree "
                       "8; the traffic file's window ends at tree 8")
    # a window the ceiling cut is not a correct run: its rate is over fewer,
    # cheaper trees
    window, cuts, reached, last = rehearse(0.001, tmp_path)
    assert re.search(r": 1 chunks of 2 trees", window), window
    assert len(cuts) == 1
    assert cuts[0].startswith("ceiling: window cut at tree 4,"), cuts
    assert last["attempted"] == 1
    assert reached == ("NOT window_reached_its_end: the window ended at tree "
                       "4; the traffic file's window ends at tree 8")
