"""Every tools/*.py must import and answer --help.

A tool that no longer imports — a renamed kernel symbol, a moved module —
rots silently until somebody needs it.  This smoke test executes each tool
as __main__ with --help inside ONE subprocess (a single jax import
amortized over all of them) and has one case per tool, so the tool that
rots is named: argparse must answer with a usage string and exit code 0
before any device work or heavy allocation starts.
"""
import glob
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = sorted(os.path.basename(p)
               for p in glob.glob(os.path.join(REPO, "tools", "*.py")))

_DRIVER = r"""
import contextlib, io, os, runpy, sys
repo = sys.argv[1]
for name in sys.argv[2:]:
    path = os.path.join(repo, "tools", name)
    sys.argv = [path, "--help"]
    buf = io.StringIO()
    code = None
    try:
        with contextlib.redirect_stdout(buf), contextlib.redirect_stderr(buf):
            runpy.run_path(path, run_name="__main__")
    except SystemExit as e:  # argparse --help exits 0
        code = 0 if e.code in (0, None) else e.code
    except BaseException as e:  # noqa: BLE001
        print("FAILED: %s: %r" % (name, e))
        continue
    out = buf.getvalue()
    if code != 0:
        print("FAILED: %s: exit code %r (%s)" % (name, code, out[:200]))
    elif "usage" not in out.lower():
        print("FAILED: %s: no usage text in --help output: %r"
              % (name, out[:200]))
    else:
        print("ok:", name)
"""


@pytest.fixture(scope="module")
def help_run():
    assert TOOLS, "no tools found"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-c", _DRIVER, REPO] + TOOLS,
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert p.returncode == 0, p.stdout + p.stderr
    return p.stdout


@pytest.mark.parametrize("name", TOOLS)
def test_every_tool_answers_help(help_run, name):
    assert "ok: %s\n" % name in help_run, [
        line for line in help_run.splitlines() if name in line]


def test_gen_params_check_in_sync():
    """``gen_params.py --check`` is the staleness tripwire for the
    embedded ``_params_meta.py`` tail: it must pass on the committed
    tree, and fail loudly when the meta file drifts from the generator
    (a hand-edited tail is exactly the rot it exists to catch)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    tool = os.path.join(REPO, "tools", "gen_params.py")
    p = subprocess.run([sys.executable, tool, "--check"],
                       capture_output=True, text=True, timeout=120,
                       env=env, cwd=REPO)
    assert p.returncode == 0, p.stdout + p.stderr
    # a drifted meta file must flunk the check, naming the problem
    import tempfile
    with open(os.path.join(REPO, "lightgbm_tpu", "_params_meta.py")) as fh:
        meta = fh.read()
    with tempfile.NamedTemporaryFile("w", suffix=".py",
                                     delete=False) as tmp:
        tmp.write(meta.replace("'hist_precision'", "'hist_drifted'", 1))
        stale = tmp.name
    try:
        p = subprocess.run([sys.executable, tool, "--check",
                            "--meta", stale],
                           capture_output=True, text=True, timeout=120,
                           env=env, cwd=REPO)
        assert p.returncode != 0, p.stdout + p.stderr
    finally:
        os.unlink(stale)


def test_aggregate_xplane_reads_a_recorded_trace(tmp_path):
    """``tools/profile_tree.py::aggregate_xplane`` (``microbench_phaseA``'s
    reader) on a trace recorded on the chip, through
    ``jax.profiler.ProfileData``: no other package is needed."""
    import gzip
    where = tmp_path / "plugins" / "profile" / "run"
    where.mkdir(parents=True)
    fixture = os.path.join(REPO, "benchmarks", "tests", "fixtures",
                           "tiny.xplane.pb.gz")
    with gzip.open(fixture, "rb") as src:
        (where / "host.xplane.pb").write_bytes(src.read())
    sys.path.insert(0, REPO)
    try:
        from tools.profile_tree import aggregate_xplane
    finally:
        sys.path.pop(0)
    rows = aggregate_xplane(str(tmp_path), top=40)
    by_name = {name: (ms, count) for name, ms, count in rows}
    # one traced chunk of 2 trees x 15 leaves: 28 split launches
    assert by_name["%partition_hist_pallas"][1] == 28
    assert by_name["%partition_hist_pallas"][0] == pytest.approx(1.964, abs=1e-3)
    assert rows == sorted(rows, key=lambda r: -r[1])


def test_no_tool_needs_tensorflow():
    hits = [p for p in glob.glob(os.path.join(REPO, "tools", "*.py"))
            + glob.glob(os.path.join(REPO, "lightgbm_tpu", "**", "*.py"),
                        recursive=True)
            if "tensorflow" in open(p).read()]
    assert hits == []
