"""The reduction from a trace to busy time, own time and idle gaps: on made-up
events, and on one small trace recorded on a TPU v5e by this harness (PR 26,
cell ``tiny_trace`` under ``tests/cells``: one traced chunk of 2 trees x 15
leaves on 65,536 x 28 rows, run as ``python3``).  The pinned numbers are what
that run printed on the chip."""
import os

import pytest

import trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "tiny.xplane.pb.gz")


def test_union_counts_overlap_once():
    events = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("d", 31, 2)]
    assert tr.union_ns(events) == 15 + 5


def test_own_time_is_duration_less_children():
    events = [("%while.1 = x", 0, 100), ("%k.1 = y", 10, 30),
              ("%cond.2 = z", 50, 40), ("%k.1 = y", 60, 10), ("%z = q", 200, 7)]
    own = tr.own_times(events)
    assert own == {"%while.1": 30, "%k.1": 40, "%cond.2": 30, "%z": 7}
    assert sum(own.values()) == tr.union_ns(events)


def test_prefix_match_takes_every_numbered_variant():
    own = {"%partition_hist_pallas.13": 2.0, "%partition_hist_pallas.14": 3.0,
           "%histogram_pallas_rows.14": 5.0, "%copy.1": 7.0}
    assert tr.own_of(own, ["%partition_hist_pallas"]) == 5.0
    assert tr.own_of(own, ["%partition_hist_pallas", "%histogram"]) == 10.0


def test_clip_and_gaps():
    events = [("a", 0, 10), ("b", 20, 10), ("c", 50, 10)]
    assert tr.clip(events, [(5, 25)]) == [("a", 5, 5), ("b", 20, 5)]
    assert tr.gaps(events, [(5, 55)]) == [(10, 20), (30, 50)]
    host = [("outer", 0, 100), ("inner", 25, 30)]
    assert tr.attribute_gaps([(10, 20), (30, 30 + 2 * tr.SHORT_GAP_NS)],
                             host) == {
        "outer: gaps under 10 us": 10,
        "inner: gaps of 10 us or more": 2 * tr.SHORT_GAP_NS}


@pytest.fixture(scope="module")
def recorded():
    return tr.load(FIXTURE)


def test_recorded_trace_lines(recorded):
    (plane, events), = recorded["device"].items()
    assert plane == "/device:TPU:0"
    # the unit spans are on the line of the thread that opened them, named
    # after the executable
    assert [k for k, v in recorded["host"].items()
            if any(n == tr.UNIT_ANNOTATION for n, _, _ in v)] == ["python3"]
    # the whole line: own times add up to the union, and a %while that
    # encloses its body does little itself
    own = tr.own_times(events)
    assert sum(own.values()) == pytest.approx(tr.union_ns(events), rel=1e-9)
    whiles = {n: ns for n, ns in own.items() if n.startswith("%while")}
    whole = sum(d for n, _, d in events if tr.op_name(n) in whiles)
    assert whiles and sum(whiles.values()) < 0.1 * whole


def test_reduce_gives_what_the_chip_run_printed():
    r = tr.reduce(FIXTURE, tr.UNIT_ANNOTATION)
    assert r["units"] == 1 and r["chips"] == 1
    assert r["busy_ns"] == pytest.approx(0.010378939e9, rel=1e-6)
    assert r["window_ns"] == pytest.approx(0.01347454e9, rel=1e-6)
    # ms per tree over the 2 traced trees, as the per-layer metrics read
    assert tr.own_of(r["own"], ["%partition_hist_pallas"]) / 2e6 \
        == pytest.approx(0.9822115, rel=1e-6)
    assert tr.own_of(r["own"], ["%histogram_pallas_rows"]) / 2e6 \
        == pytest.approx(0.3218145, rel=1e-6)
    assert sum(r["own"].values()) == pytest.approx(r["busy_ns"], rel=1e-9)
    assert sum(r["idle"].values()) == pytest.approx(
        r["window_ns"] - r["busy_ns"], rel=1e-9)
    assert all(k.startswith(("bench.unit: ", "PjitFunction")) for k in r["idle"])
    with pytest.raises(ValueError):
        tr.reduce(FIXTURE, "no.such.span")
