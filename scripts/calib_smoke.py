#!/usr/bin/env python
"""Calibration-observatory smoke (ISSUE 19): the full drift story e2e.

Four phases on the CPU 2pc-3 anchor, one command:

  A. cold run         — comparator populates `detail["calib"]` and flushes
                        durable observation records (obs/calib.py).
  B. mis-scaled model — a deliberately wrong coefficient overlay
                        (SR_TPU_COSTMODEL_CALIB) trips the drift detector:
                        `calib.drift_*` counters, the journaled
                        `calib.drift` event, and the timeline CLI report
                        naming engine/term/jobs. Search results stay
                        bit-identical — the observatory observes, never
                        steers.
  C. fit              — `tpu_tune --calibrate` least-squares-fits theta
                        from phase-B's recorded observations and writes a
                        fitted overlay.
  D. fitted run       — the fitted overlay pulls measured/predicted back
                        toward 1 (>=2x closer than the mis-scaled run).

    JAX_PLATFORMS=cpu python scripts/calib_smoke.py [--keep]

Exit code 0 iff every check passes. Artifacts land in a temp dir (kept
with --keep, printed either way).
"""

import json
import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv) -> int:
    from stateright_tpu.obs.calib import default_device_kind, theta_of
    from stateright_tpu.service import CheckService
    from stateright_tpu.tensor import costmodel as cm
    from stateright_tpu.tensor.models import TensorTwoPhaseSys

    keep = "--keep" in argv
    outdir = tempfile.mkdtemp(prefix="calib_smoke_")
    failures = []

    def check(ok: bool, what: str):
        print(("ok   " if ok else "FAIL ") + what)
        if not ok:
            failures.append(what)

    # Small chunks so the short anchor run closes several comparison
    # windows (K=3 consecutive out-of-band chunks arm a drift episode).
    os.environ["SR_TPU_CALIB_CHUNK"] = "4"
    kind = default_device_kind()
    stock = cm.stock_device(kind)
    model = TensorTwoPhaseSys(3)

    def run_phase(tag: str, repeats: int = 1):
        """One service run; returns (results, calib detail, counters)."""
        os.environ["SR_TPU_CALIB_DIR"] = os.path.join(outdir, f"rec_{tag}")
        svc = CheckService(
            batch_size=128, table_log2=12, background=False,
            events_out=os.path.join(outdir, f"journal_{tag}.jsonl"),
        )
        results = []
        for _ in range(repeats):
            h = svc.submit(model)
            svc.drain(timeout=600)
            results.append(h.result())
        calib = (results[-1].detail or {}).get("calib")
        counters = (
            svc._engine._calib.metrics()
            if svc._engine._calib is not None else {}
        )
        svc.close()
        return results, calib, counters

    # -- A: cold run, stock coefficients ---------------------------------
    os.environ.pop("SR_TPU_COSTMODEL_CALIB", None)
    res_a, calib_a, _ = run_phase("a")
    golden = (res_a[0].state_count, res_a[0].unique_state_count)
    check(calib_a is not None and calib_a["chunks"] > 0,
          f"A: comparator populated ({calib_a and calib_a['chunks']} chunks, "
          f"drift_ratio {calib_a and calib_a['drift_ratio']})")
    check(os.path.isdir(os.path.join(outdir, "rec_a", "calib")),
          "A: durable observation records flushed")

    # -- B: deliberately mis-scaled overlay ------------------------------
    # Every bandwidth 1000x too fast, every per-element/dispatch term
    # 1000x too small: predicted collapses toward 0, measured/predicted
    # blows out the [0.7, 1.4] band on every chunk.
    bad = {
        "base": kind,
        "rates": {
            "gbps_gather": stock.gbps_gather * 1e3,
            "gbps_sort": stock.gbps_sort * 1e3,
            "gbps_scatter": stock.gbps_scatter * 1e3,
            "gbps_stream": stock.gbps_stream * 1e3,
            "ns_expand_elem": stock.ns_expand_elem / 1e3,
            "ns_other_lane": stock.ns_other_lane / 1e3,
            "ms_dispatch": stock.ms_dispatch / 1e3,
            "pcie_gbps": stock.pcie_gbps * 1e3,
        },
    }
    bad_path = os.path.join(outdir, "bad_overlay.json")
    with open(bad_path, "w") as f:
        json.dump(bad, f)
    os.environ["SR_TPU_COSTMODEL_CALIB"] = bad_path
    res_b, calib_b, counters_b = run_phase("b", repeats=3)
    check(all((r.state_count, r.unique_state_count) == golden
              for r in res_b),
          "B: search results bit-identical under mis-scaled overlay")
    check(counters_b.get("drift_events", 0) >= 1
          and counters_b.get("out_of_band", 0) >= 3,
          f"B: drift detector tripped (drift_events="
          f"{counters_b.get('drift_events')}, out_of_band="
          f"{counters_b.get('out_of_band')})")
    journal_b = os.path.join(outdir, "journal_b.jsonl")
    drifted = [
        json.loads(line) for line in open(journal_b)
        if '"calib.drift"' in line
    ]
    check(len(drifted) >= 1 and drifted[0].get("engine") == "service"
          and drifted[0].get("term"),
          f"B: calib.drift journaled (term {drifted and drifted[0]['term']})")

    # Timeline CLI names job/engine/term — and drift is NOT an anomaly.
    tl = subprocess.run(
        [sys.executable, "-m", "stateright_tpu.obs.timeline",
         journal_b, "--json"],
        capture_output=True, text=True, cwd=REPO,
    )
    check(tl.returncode == 0, f"timeline: exit 0 (got {tl.returncode})")
    rep = json.loads(tl.stdout) if tl.stdout.strip() else {}
    rows = rep.get("drift") or []
    check(bool(rows) and rows[0].get("engine") and rows[0].get("term"),
          f"timeline: drift report names engine/term ({rows[:1]})")
    check(not rep.get("anomalies"),
          "timeline: drift is not a lifecycle anomaly")

    # -- C: fit from the recorded observations ---------------------------
    fit_path = os.path.join(outdir, "fit_overlay.json")
    fit = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "tpu_tune.py"),
         "--calibrate", os.path.join(outdir, "rec_b"),
         "--device", kind, "--out", fit_path],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    sys.stdout.write(fit.stdout)
    check(fit.returncode == 0 and os.path.exists(fit_path),
          "C: tpu_tune --calibrate wrote the fitted overlay")
    overlay = json.load(open(fit_path))
    check(overlay.get("base") == kind
          and len(overlay.get("theta", [])) == len(theta_of(stock)),
          "C: overlay is the loadable costmodel shape")

    # -- D: fitted overlay restores the band -----------------------------
    os.environ["SR_TPU_COSTMODEL_CALIB"] = fit_path
    res_d, calib_d, _ = run_phase("d")
    check(all((r.state_count, r.unique_state_count) == golden
              for r in res_d),
          "D: search results bit-identical under fitted overlay")
    drift_b = abs(calib_b["drift_ratio"] - 1.0)
    drift_d = abs(calib_d["drift_ratio"] - 1.0)
    check(drift_d * 2 <= drift_b,
          f"D: fitted overlay >=2x closer to measured "
          f"(|ratio-1| {drift_b:.3f} -> {drift_d:.3f})")
    lo, hi = 0.7, 1.4
    in_band = lo <= calib_d["drift_ratio"] <= hi
    print(f"     D drift_ratio {calib_d['drift_ratio']:.3f} "
          f"({'inside' if in_band else 'outside'} the [{lo}, {hi}] band; "
          "CPU step times are compile/noise-heavy, the >=2x restoration "
          "above is the pinned check)")

    print(f"artifacts in {outdir}" + ("" if keep else " (temp)"))
    if failures:
        print(f"{len(failures)} FAILURE(S)")
        return 1
    print("calib smoke: all checks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
