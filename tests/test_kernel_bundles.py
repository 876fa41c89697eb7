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


def _nest(depths):
    """Bundles of one two-bundle loop body a depth, in that order."""
    out = []
    for d in depths:
        out += [KB.Bundle(d, True, ["vld"]), KB.Bundle(d, False, ["vst"])]
    return out


# as the compiler dumps the c4096 kernel since PR 40 (the drain's four tail
# loops are new)
PIPELINED_DEPTHS = [1, 2, 3, 3, 3, 3, 1, 2, 2, 2, 2, 1, 1, 1, 1, 1, 1,
                    1, 2, 1, 2, 1, 2]


def test_the_copy_back_has_an_outer_and_an_inner_body_by_name():
    loops = KB.loop_bodies(_nest(PIPELINED_DEPTHS))
    names = KB.loop_names(loops)
    assert len(names) == len(loops) == 23
    assert [n for n in names if "tail" in n] == [
        "drain: tail_left start, a tile", "drain: tail_right start, a tile",
        "drain: tail_left wait, a tile", "drain: tail_right wait, a tile"]
    assert names[2:6] == ["await_left, a block", "await_right, a block",
                          "flush_left start, a block",
                          "flush_right start, a block"]
    assert names[0].startswith("pipe_body") and names[1].startswith("chunk_c")
    assert names[-2:] == ["copy-back cb_chunk: a chunk read of the scratch",
                          "copy-back cb_tile: a 128-row tile"]
    text = KB.report(_nest(PIPELINED_DEPTHS), nest=KB.PIPELINED_LOOPS)
    assert "  loop at bundle 42 [copy-back cb_chunk: a chunk read of the " \
        "scratch]: 2 own bundles (2 more in nested loops)" in text
    assert "    loop at bundle 44 [copy-back cb_tile: a 128-row tile]: 2 " \
        "own bundles (0 more in nested loops)" in text


def test_the_group_block_is_named_and_shared_out_a_group():
    """The hist pass's inner body is a BLOCK of feature groups since PR 37
    (one extraction dot, k group steps): named so, and with k given its
    bundles are also printed a group."""
    loops = KB.loop_bodies(_nest(PIPELINED_DEPTHS))
    names = KB.loop_names(loops)
    assert [n for n in names if "group" in n] == [KB.GROUP_BLOCK] * 2
    assert names[names.index(KB.GROUP_BLOCK) - 1].startswith("hist_pass")
    text = KB.report(_nest(PIPELINED_DEPTHS), nest=KB.PIPELINED_LOOPS,
                     groups_a_block=2)
    assert text.count("[a block of feature groups]: 2 own bundles (0 more "
                      "in nested loops) = 1 a feature group (2 a block)") == 2
    assert "a feature group (" not in KB.report(
        _nest(PIPELINED_DEPTHS), nest=KB.PIPELINED_LOOPS)


@pytest.mark.parametrize("depths", [
    PIPELINED_DEPTHS[:-1],                    # PR 32's: one copy-back body
    PIPELINED_DEPTHS[:13] + PIPELINED_DEPTHS[17:],   # PR 39's: no tail loops
    [1],                                      # the small kernel has no loop nest
    PIPELINED_DEPTHS + [1],
])
def test_another_loop_nest_goes_unnamed(depths):
    assert KB.loop_names(KB.loop_bodies(_nest(depths))) is None
    text = KB.report(_nest(depths), nest=KB.PIPELINED_LOOPS)
    assert "bodies unnamed" in text and "[" not in text.split("\n", 2)[2]
    assert "unnamed" not in KB.report(_nest(depths))
