#!/usr/bin/env python
"""Gate a run's telemetry summary against the declared counts.

    python tools/perf_gate.py out.jsonl.summary.json [more.summary.json ...]

Reads the ``budgets`` of ``PERF_BUDGETS.json`` (repo root; ``--budgets``
overrides) and fails a summary (``<telemetry_out>.summary.json``) on what a
run COUNTS, never on what it timed:

- ``recompiles_steady`` -- the ``recompiles_timed_window`` gauge a driver
  pins after warm-up (a plain run includes its warm-up compiles and
  carries no such gauge);
- ``serving_dropped`` / ``serving_rejected_max`` / ``serving_failed_max``
  -- the serving tier's never-drop contract;
- ``alerts_fired_max`` -- live alerts a healthy run fired;
- ``plan_cache_fallbacks_max`` -- tuned-plan cache reads that fell back,
  and every stamped plan site names a known provenance;
- a watchdog stall recorded by the resilience plane.

Speed is not gated here: it is measured on the chip by
``benchmarks/run.py`` and recorded in ``PERF_LEDGER.jsonl``.  Exit status:
0 all pass, 1 any breach, 2 an unreadable file or one that is not a
telemetry summary.
"""
import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DEFAULT_BUDGETS = os.path.join(REPO, "PERF_BUDGETS.json")

KNOWN_PROVENANCE = ("analytic", "tuned", "pinned")


class Gate:
    """Collects per-check verdicts; one summary yields several."""

    def __init__(self):
        self.failures = 0
        self.checks = 0

    def check(self, artifact: str, name: str, ok: bool, detail: str) -> None:
        self.checks += 1
        if not ok:
            self.failures += 1
        print("%s %s: %s (%s)" % ("PASS" if ok else "FAIL",
                                  os.path.basename(artifact), name, detail))

    def at_most(self, artifact: str, name: str, value, budget) -> None:
        self.check(artifact, name, int(value) <= int(budget),
                   "%s <= %s" % (value, budget))


def _load(path: str):
    with open(path) as fh:
        return json.load(fh)


def gate_summary(g: Gate, path: str, doc: dict, b: dict) -> None:
    gauges = doc.get("gauges") or {}
    if gauges.get("recompiles_timed_window") is not None:
        g.at_most(path, "recompiles steady",
                  gauges["recompiles_timed_window"],
                  b.get("recompiles_steady", 0))
    res = doc.get("resilience") or {}
    if res.get("watchdog_stall_s") is not None:
        g.check(path, "no watchdog stall", False,
                "watchdog_stall_s=%s" % res["watchdog_stall_s"])
    srv = doc.get("serving")
    if srv:
        g.at_most(path, "serving failed", srv.get("failed", 0),
                  b.get("serving_failed_max", 0))
        g.at_most(path, "serving rejected", srv.get("rejected", 0),
                  b.get("serving_rejected_max", 0))
        if srv.get("dropped") is not None:
            g.at_most(path, "serving dropped", srv["dropped"],
                      b.get("serving_dropped", 0))
    al = doc.get("alerts")
    if al is not None:
        g.at_most(path, "alerts fired", al.get("fired_total", 0),
                  b.get("alerts_fired_max", 0))
    # a summary from a run with no planner site carries no plan block
    plan = doc.get("plan")
    if plan is not None:
        sites = plan.get("sites") or {}
        g.check(path, "plan provenance",
                bool(sites) and all(i.get("provenance") in KNOWN_PROVENANCE
                                    for i in sites.values()),
                "%s over sites %s" % (plan.get("provenance"),
                                      sorted(sites) or "none"))
        if plan.get("cache_fallbacks") is not None:
            g.at_most(path, "plan cache fallbacks", plan["cache_fallbacks"],
                      b.get("plan_cache_fallbacks_max", 0))


def run_gate(summaries, budgets_path: str) -> int:
    try:
        b = _load(budgets_path).get("budgets") or {}
    except (OSError, ValueError, AttributeError) as exc:
        print("cannot read budgets %s: %s" % (budgets_path, exc),
              file=sys.stderr)
        return 2
    g = Gate()
    rc = 0
    for path in summaries:
        try:
            doc = _load(path)
        except (OSError, ValueError) as exc:
            print("cannot read summary %s: %s" % (path, exc),
                  file=sys.stderr)
            rc = 2
            continue
        if not isinstance(doc, dict) or doc.get("metric") != "telemetry_run":
            print("%s is not a telemetry summary (no metric=telemetry_run)"
                  % path, file=sys.stderr)
            rc = 2
            continue
        gate_summary(g, path, doc, b)
    print("perf gate: %d checks, %d failed" % (g.checks, g.failures))
    return 1 if g.failures else rc


def build_parser():
    ap = argparse.ArgumentParser(
        description="gate telemetry summaries against the declared counts "
                    "(PERF_BUDGETS.json); nonzero exit on any breach")
    ap.add_argument("summaries", nargs="+",
                    help="telemetry <out>.summary.json paths")
    ap.add_argument("--budgets", default=DEFAULT_BUDGETS,
                    help="budgets spec (default: repo PERF_BUDGETS.json)")
    return ap


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    return run_gate(args.summaries, args.budgets)


if __name__ == "__main__":
    sys.exit(main())
