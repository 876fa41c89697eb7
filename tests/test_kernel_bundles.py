"""tools/kernel_bundles.py: the parser of the compiler's final-bundles dump,
on a fixture cut from a real one (the c4096 kernel for a described v5e; ops
past the third of a bundle dropped).  The compile itself is the tool's, by
hand: it loads the TPU's library in a child process."""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import kernel_bundles as KB  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "final_bundles_fixture.txt")


@pytest.fixture(scope="module")
def bundles():
    with open(FIXTURE) as fh:
        return KB.parse_bundles(fh)


def test_every_bundle_line_is_parsed_and_the_key_is_not(bundles):
    assert len(bundles) == 22
    assert [b.depth for b in bundles] == (
        [0] * 3 + [1] * 5 + [2] * 3 + [1, 0, 1, 1, 0] + [1] * 6)
    assert [i for i, b in enumerate(bundles) if b.loop_start] == [3, 8, 16]
    assert bundles[5].ops == ["sld", "sdivrem.u32"]
    assert bundles[12].ops == []              # an empty branch shadow


@pytest.mark.parametrize("which,at,depth,own,nested,segments,top", [
    (0, 3, 1, 9, 3, [5, 1, 2], ("sld", 3)),   # shadow inside, one after: not its
    (1, 8, 2, 3, 0, [3], ("sst", 3)),
    (2, 16, 1, 6, 0, [6], ("ssub.s32", 2)),
])
def test_loop_bodies(bundles, which, at, depth, own, nested, segments, top):
    lp = KB.loop_bodies(bundles)[which]
    assert (lp["at"], lp["depth"], lp["own"], lp["nested"]) == (
        at, depth, own, nested)
    assert lp["segments"] == segments
    assert lp["ops"].most_common(1)[0] == top


def test_report_folds_repeated_segments():
    assert KB._runs([79, 52, 52, 52, 45]) == "79, 3 x 52, 45"
    b = [KB.Bundle(1, True, ["vld"])] + [KB.Bundle(1, False, ["vst"])] * 2
    text = KB.report(b + [KB.Bundle(0, False, [])] + b[1:], top=1)
    assert "loop at bundle 0: 6 own bundles (0 more in nested loops)" in text
    assert "segments: 3, 2" in text and "ops: vst 4" in text
