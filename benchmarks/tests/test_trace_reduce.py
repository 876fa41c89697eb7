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
    # a gap is cut where a span begins or ends: the long one starts under
    # "inner" (25-55), goes on under "outer" (to 100) and outlasts both
    assert tr.attribute_gaps([(10, 20), (30, 30 + 2 * tr.SHORT_GAP_NS)],
                             host) == {
        "outer: gaps under 10 us": 10,
        "inner: gaps of 10 us or more": 25,
        "outer: gaps of 10 us or more": 45,
        "no host span: gaps of 10 us or more": 2 * tr.SHORT_GAP_NS - 70}


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
    # no name of the program's handed over: the unit's span leads
    assert all(k.startswith(("bench.unit: ", "bench.unit > "))
               for k in r["idle"])
    assert "bench.unit > PjitFunction(converted): gaps of 10 us or more" \
        in r["idle"]
    with pytest.raises(ValueError):
        tr.reduce(FIXTURE, "no.such.span")


# ---- the asynchronous line, and the program's names on the gaps ------------

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
