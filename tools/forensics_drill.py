#!/usr/bin/env python
"""End-to-end performance-forensics drill (round 16).

Proves the whole forensics plane live on this box.  Trains a small model,
arms EVERYTHING (alert engine with a doctored p99 rule, flight recorder,
live exporter), serves traffic, and asserts: a burn-rate alert fires on
``/alerts``; the alert triggers EXACTLY ONE profiler capture artifact
(bounded, never recursive); ``/metrics`` scrapes well-formed with compile
accounting (and device-memory gauges on backends that report them);
steady-state recompiles stay 0 with everything armed.  Exit 0 = the
acceptance drill passed.

Small CPU shapes; runs anywhere with ``JAX_PLATFORMS=cpu``.
"""
import argparse
import glob
import json
import os
import sys
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _build(n=4096, iters=8):
    import numpy as np
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.objective import create_objective
    rng = np.random.RandomState(0)
    X = rng.normal(size=(n, 8)).astype(np.float32)
    y = X[:, 0] * 1.5 + np.sin(X[:, 1]) + rng.normal(scale=0.1, size=n)
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=16)
    cfg = Config(objective="regression", num_leaves=15, min_data_in_leaf=5,
                 num_iterations=iters, verbosity=-1)
    return GBDT(cfg, ds, create_objective("regression", cfg)), X


def _get(port, path, timeout=90):
    return urllib.request.urlopen(
        "http://127.0.0.1:%d%s" % (port, path), timeout=timeout).read(
    ).decode()


def run_drill(workdir: str) -> int:
    from lightgbm_tpu import obs
    from lightgbm_tpu.obs.exporter import start_exporter
    from lightgbm_tpu.serving import Server
    out = os.path.join(workdir, "drill.jsonl")
    rules = [
        # DOCTORED p99 bar: any real serving latency breaches it, so the
        # drill proves the burn-rate path end to end
        {"name": "drill_p99", "kind": "quantile",
         "metric": "serve_latency_s_model_*", "quantile": "p99",
         "max": 1e-5, "budget": 0.0, "fast_window_s": 5,
         "slow_window_s": 10, "severity": "page"},
    ]
    booster, X = _build()
    tele = obs.configure(out=out, freq=1, flight_recorder=True,
                         entry="forensics_drill")
    from lightgbm_tpu.obs import alerts as obs_alerts
    obs_alerts.install(tele, rules=rules, interval_s=0.1)
    exp = start_exporter(tele, port=0)
    try:
        booster.train_chunk(4)
        booster.train_chunk(4)  # steady chunk: prices the fused compile
        with Server(max_batch_wait_us=0) as srv:
            srv.register("drill", booster)
            for _ in range(4):
                srv.predict("drill", X[:64])
            # 1) the doctored breach fires on /alerts
            deadline = time.time() + 30
            fired = None
            while time.time() < deadline:
                a = json.loads(_get(exp.port, "/alerts"))
                if a.get("firing"):
                    fired = a
                    break
                time.sleep(0.2)
            assert fired, "no alert fired within 30s: %r" % (a,)
            assert any(st["rule"] == "drill_p99" and st["state"] == "firing"
                       for st in fired["series"]), fired
            print("PASS alert: drill_p99 firing on /alerts "
                  "(fired_total=%d)" % fired["fired_total"])
            # 2) the alert triggered EXACTLY ONE capture (flight recorder
            # is one-shot; the profiler session start can take ~10s cold)
            # poll for the RECORDED capture (auto_fired flips before the
            # capture thread starts, so fired+idle alone is not "done")
            deadline = time.time() + 120
            while time.time() < deadline:
                st = tele.profiling
                if st is not None and st.captures and not st.active:
                    break
                time.sleep(0.5)
            caps = sorted(glob.glob(os.path.join(out + ".profiles",
                                                 "capture_*")))
            assert len(caps) == 1, \
                "expected exactly 1 capture artifact, got %r" % caps
            assert os.path.exists(os.path.join(caps[0], "capture.json")), \
                "capture dir %s has no capture.json" % caps[0]
            # a second incident must NOT capture again (bounded)
            from lightgbm_tpu.obs import profiling
            assert profiling.on_incident("drill_second") is None
            caps2 = glob.glob(os.path.join(out + ".profiles", "capture_*"))
            assert len(caps2) == 1, caps2
            print("PASS capture: exactly one flight-recorder artifact at %s"
                  % caps[0])
            # 3) /metrics scrapes well-formed with the forensics gauges
            m = _get(exp.port, "/metrics")
            assert "lgbm_tpu_compile_seconds_total" in m, m[:400]
            assert "lgbm_tpu_residency_bytes" in m
            assert "lgbm_tpu_alert_state" in m
            have_dev = "lgbm_tpu_device_bytes_in_use" in m
            for line in m.splitlines():
                assert line.startswith("#") or " " in line, line
            print("PASS scrape: compile%s/residency/alert gauges "
                  "well-formed on /metrics"
                  % ("/devmem" if have_dev else ""))
            # 4) steady-state recompiles stay 0 with everything armed
            obs.recompile.reset()
            booster.train_chunk(4)
            for _ in range(4):
                srv.predict("drill", X[:64])
            steady = obs.recompile.total()
            assert steady == 0, \
                "steady-state recompiles %d != 0 with forensics armed" \
                % steady
            print("PASS steady: recompiles 0 through armed train+serve")
        acct = tele.compile_acct.snapshot()
        assert acct.get("keys"), "compile accounting recorded nothing"
        print("PASS compile accounting: %d key(s), %.4gs total"
              % (len(acct["keys"]), acct["compile_seconds_total"]))
    finally:
        obs.disable()
    print("FORENSICS DRILL PASSED")
    return 0


def build_parser():
    ap = argparse.ArgumentParser(
        description="end-to-end performance-forensics drill (doctored p99 "
                    "breach -> burn-rate alert -> one flight-recorder "
                    "capture; /metrics well-formed; steady recompiles 0)")
    ap.add_argument("--workdir", default=None,
                    help="scratch dir (default: a fresh tempdir)")
    return ap


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    import tempfile
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    workdir = args.workdir or tempfile.mkdtemp(prefix="forensics_drill_")
    from lightgbm_tpu.utils.log import Log
    Log.reset_level(30)
    return run_drill(workdir)


if __name__ == "__main__":
    sys.exit(main())
