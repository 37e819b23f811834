"""Real-TPU tuning sweep for the resident engine on the north-star workload.

Single-config mode runs one (workload, batch, table, layout) on the DEFAULT
jax backend, asserting golden parity and printing states/sec — one config
per invocation, so a failing config cannot take the sweep down with it.

Sweep mode makes a chip run a single command: it races
insert_variant x batch in subprocess-isolated single-config runs, collects
the machine-readable RESULT_JSON line each prints, joins the measurements
with the cost model's committed predictions (tensor/costmodel.py), and
dumps a ranking JSON.

Usage:
  python scripts/tpu_tune.py MODEL N BATCH TABLE_LOG2 [REPEATS] [LAYOUT] \
      [STORE] [HIGH_WATER] [SUMMARY_LOG2]
  python scripts/tpu_tune.py --sweep MODEL N TABLE_LOG2 \
      [--batches 2048,4096,8192] [--variants split,kv,phased,capped,pallas] \
      [--stores device,tiered] [--high-waters 0.85] [--summary-bits 20] \
      [--repeats R] [--timeout SEC] [--out tune_ranking.json]
  python scripts/tpu_tune.py sim MODEL N TRACES DEDUP [WALKS] [MAX_DEPTH] \
      [REPEATS] [TABLE_LOG2]
  python scripts/tpu_tune.py --sweep MODEL N TABLE_LOG2 --sim \
      [--traces 1024,2048,4096] [--dedup trace,shared] [--walks W] \
      [--max-depth D] [--repeats R] [--timeout SEC] [--out ...]
  python scripts/tpu_tune.py --calibrate ROOT \
      [--device KIND] [--ridge R] [--out overlay.json]

`--calibrate` is the calibration-observatory fitter (obs/calib.py): it
loads every durable observation record the comparators flushed under
ROOT (a store root or blob:// URI; records land in ROOT/calib/),
least-squares-fits the costmodel coefficient vector per device kind,
prints stock-vs-fitted rates plus a leave-one-key-out holdout table,
writes the loadable overlay JSON (activate with
SR_TPU_COSTMODEL_CALIB=<overlay>), and re-evaluates the two committed
pre-hardware rankings (r12 capped-vs-pallas insert crossover, r18
sim-walk shared-table overhead) under the fitted coefficients, printing
whether either committed default flips.

The `sim` forms race the fourth engine (tensor/simulation.py, the device
random-walk checker): `--sim` switches the sweep axes to traces x dedup
(DEDUP values: trace | shared — knobs.SIM_DEDUP_KINDS; shared runs the
global visited table so walks/s AND real unique coverage are measured),
ranking configs by walks/s next to the costmodel's committed
sim_step_cost/sim_walks_per_sec predictions.

LAYOUT / --variants values: split (default) | kv | phased | capped |
capped-kv | capped-phased | pallas — the visited-table designs to race
(kv = interleaved buckets; phased = pre-sort-claim scatter-max insert;
capped = batch-monotonic claim-tile insert, see
hashtable.make_capped_insert; pallas = the partitioned-VMEM
route-then-probe kernel, tensor/pallas_hashtable.py — the SURVEY §7
end-state design; needs table_log2 >= 10 and runs interpret-mode off-TPU).

STORE / --stores values: device (default) | tiered — the two-tier state
store (stateright_tpu/store/: device hot set + host spill tier). With
--stores including "tiered", the sweep races every water-mark x summary-bit
combination from --high-waters / --summary-bits alongside the insert
variants, so one chip run prices the spill machinery.
(tiered composes with the split-layout insert variants only.)

Set TPU_TUNE_TRACE=/path to capture a jax.profiler trace of the timed runs
(inspect with tensorboard or xprof to see the per-step op breakdown).
"""
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: F401

from bench import GOLDEN  # one golden table
from stateright_tpu.compile_cache import use_compile_cache

use_compile_cache()

# LAYOUT name -> (table_layout, insert_variant) engine options. The
# costmodel variant for predicted_ms comes from the shared
# costmodel.ENGINE_VARIANTS mapping (one source of truth with bench.py).
LAYOUTS = {
    "split": ("split", "sort"),
    "kv": ("kv", "sort"),
    "phased": ("split", "phased"),
    "capped": ("split", "capped"),
    "capped-kv": ("kv", "capped"),
    "capped-phased": ("split", "capped-phased"),
    "pallas": ("split", "pallas"),
}


def _build_model(model_name: str, n: int):
    if model_name == "paxos":
        from stateright_tpu.tensor.paxos import TensorPaxos

        return TensorPaxos(client_count=n)
    if model_name in ("inclock", "inclock-sym"):
        from stateright_tpu.tensor.models import TensorIncrementLock

        return TensorIncrementLock(n, symmetry=model_name == "inclock-sym")
    from stateright_tpu.tensor.models import TensorTwoPhaseSys

    return TensorTwoPhaseSys(n)


def run_single(model_name, n, batch, table_log2, repeats, layout,
               store="device", high_water=0.85, summary_log2=20) -> int:
    if layout not in LAYOUTS:
        print(f"unknown LAYOUT {layout!r} ({' | '.join(LAYOUTS)})")
        return 2
    table_layout, insert_variant = LAYOUTS[layout]

    from stateright_tpu.tensor.resident import ResidentSearch

    model = _build_model(model_name, n)
    store_desc = (
        f" store=tiered(hw={high_water},sb={summary_log2})"
        if store == "tiered"
        else ""
    )
    print(
        f"devices={jax.devices()} workload={model_name}-{n} "
        f"batch={batch} table=2^{table_log2} layout={layout}{store_desc}",
        flush=True,
    )
    search = ResidentSearch(
        model,
        batch_size=batch,
        table_log2=table_log2,
        table_layout=table_layout,
        insert_variant=insert_variant,
        store=store,
        high_water=high_water,
        summary_log2=summary_log2,
    )
    t0 = time.monotonic()
    r = search.run()
    compile_s = time.monotonic() - t0
    print(f"compile+first: {compile_s:.1f}s", flush=True)
    trace_dir = os.environ.get("TPU_TUNE_TRACE")
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    best = None
    try:
        for i in range(repeats):
            # Tiered runs are chunked and retain a carry across run()
            # calls; without the reset every repeat would be a no-op
            # resume "measuring" near-zero durations (the 2pc-10 bench
            # lesson). Whole-search engines start fresh regardless.
            search.reset()
            r = search.run()
            print(
                f"  run {i}: {r.duration:.4f}s "
                f"({r.state_count / max(r.duration, 1e-9):,.0f} states/s, "
                f"steps={r.steps})",
                flush=True,
            )
            if best is None or r.duration < best.duration:
                best = r
    finally:
        if trace_dir:
            # Flush even when a run dies mid-loop — that is exactly when
            # the trace explains the failure.
            jax.profiler.stop_trace()
            print(f"profiler trace written to {trace_dir}", flush=True)
    gold = GOLDEN.get((model_name, n))
    parity_ok = gold is None or (
        (best.state_count, best.unique_state_count) == gold
    )
    sps = best.state_count / max(best.duration, 1e-9)
    # Machine-readable line the sweep driver parses.
    rec = {
        "workload": f"{model_name}-{n}",
        "batch": batch,
        "table_log2": table_log2,
        "layout": layout,
        "store": store,
        "sec": round(best.duration, 4),
        "states_per_sec": round(sps, 1),
        "steps": best.steps,
        "compile_sec": round(compile_s, 1),
        "parity_ok": parity_ok,
    }
    if store == "tiered":
        rec["high_water"] = high_water
        rec["summary_log2"] = summary_log2
        stats = search.store_stats()
        if stats:
            rec.update(
                {
                    k: stats[k]
                    for k in ("hot_fill", "spilled_states", "spill_events")
                }
            )
    print("RESULT_JSON " + json.dumps(rec), flush=True)
    if not parity_ok:
        print(
            f"PARITY FAIL: {best.state_count}/{best.unique_state_count} "
            f"!= {gold}"
        )
        return 1
    print(
        f"BEST {model_name}-{n} b={batch} t={table_log2}: "
        f"{best.duration:.4f}s {sps:,.0f}/s"
    )
    return 0


def run_sim_single(model_name, n, traces, dedup, walks, max_depth,
                   repeats, table_log2) -> int:
    """One simulation-engine config: repeated rounds on a fresh engine per
    repeat (the rounds loop is cumulative by design), reporting walks/s and
    the walk-plane telemetry digest as the RESULT_JSON line."""
    from stateright_tpu.knobs import SIM_DEDUP_KINDS
    from stateright_tpu.tensor.simulation import DeviceSimulation

    if dedup not in SIM_DEDUP_KINDS:
        print(f"unknown DEDUP {dedup!r} ({' | '.join(SIM_DEDUP_KINDS)})")
        return 2
    model = _build_model(model_name, n)
    print(
        f"devices={jax.devices()} workload={model_name}-{n} sim "
        f"traces={traces} dedup={dedup} walks={walks} depth={max_depth}",
        flush=True,
    )

    def fresh():
        return DeviceSimulation(
            model, seed=7, traces=traces, max_depth=max_depth,
            dedup=dedup, table_log2=table_log2, walks=walks,
        )

    t0 = time.monotonic()
    fresh().run()
    compile_s = time.monotonic() - t0
    print(f"compile+first: {compile_s:.1f}s", flush=True)
    best = None
    for i in range(repeats):
        sim = fresh()  # same seed per repeat: bit-identical rounds
        t0 = time.monotonic()
        r = sim.run()
        sec = time.monotonic() - t0
        tel = r.detail["telemetry"]
        print(
            f"  run {i}: {sec:.4f}s ({tel['walks'] / max(sec, 1e-9):,.0f} "
            f"walks/s, {r.state_count / max(sec, 1e-9):,.0f} states/s, "
            f"lane_util={tel['lane_util']})",
            flush=True,
        )
        if best is None or sec < best[0]:
            best = (sec, r, tel)
    sec, r, tel = best
    rec = {
        "workload": f"{model_name}-{n}",
        "sim": True,
        "traces": traces,
        "dedup": dedup,
        "walks": tel["walks"],
        "max_depth": max_depth,
        "table_log2": table_log2,
        "sec": round(sec, 4),
        "walks_per_sec": round(tel["walks"] / max(sec, 1e-9), 1),
        "states_per_sec": round(r.state_count / max(sec, 1e-9), 1),
        "unique": r.unique_state_count,
        "lane_util": tel["lane_util"],
        "restarts": tel["restarts"],
        "compile_sec": round(compile_s, 1),
        "parity_ok": True,  # simulation has no exhaustive golden to pin
    }
    if dedup == "shared":
        rec["dedup_hit_rate"] = tel["dedup_hit_rate"]
    print("RESULT_JSON " + json.dumps(rec), flush=True)
    print(
        f"BEST {model_name}-{n} sim traces={traces} dedup={dedup}: "
        f"{rec['walks_per_sec']:,.0f} walks/s"
    )
    return 0


def run_sweep(argv: list) -> int:
    def opt(name, default):
        if name in argv:
            i = argv.index(name)
            if i + 1 >= len(argv):
                raise SystemExit(f"missing value for {name} (see --help)")
            v = argv[i + 1]
            del argv[i : i + 2]
            return v
        return default

    sim = "--sim" in argv
    if sim:
        argv.remove("--sim")
    traces_axis = [int(t) for t in opt("--traces", "1024,2048,4096").split(",")]
    dedup_axis = opt("--dedup", "trace,shared").split(",")
    sim_walks = opt("--walks", None)
    sim_depth = int(opt("--max-depth", "256"))
    batches = [int(b) for b in opt("--batches", "2048,4096,8192").split(",")]
    variants = opt("--variants", "split,kv,phased,capped,pallas").split(",")
    stores = opt("--stores", "device").split(",")
    high_waters = [float(x) for x in opt("--high-waters", "0.85").split(",")]
    summary_bits = [int(x) for x in opt("--summary-bits", "20").split(",")]
    repeats = int(opt("--repeats", "3"))
    timeout = float(opt("--timeout", "900"))
    out_path = opt("--out", "tune_ranking.json")
    if len(argv) < 3:  # re-check arity AFTER option pairs are stripped
        print(__doc__)
        return 2
    model_name, n, table_log2 = argv[0], int(argv[1]), int(argv[2])

    if sim:
        return run_sim_sweep(
            model_name, n, table_log2, traces_axis, dedup_axis,
            sim_walks, sim_depth, repeats, timeout, out_path,
        )

    bad = [v for v in variants if v not in LAYOUTS]
    if bad:
        print(f"unknown variants {bad} ({' | '.join(LAYOUTS)})")
        return 2
    bad = [s for s in stores if s not in ("device", "tiered")]
    if bad:
        print(f"unknown stores {bad} (device | tiered)")
        return 2
    # Store axis: the plain device store plus every requested
    # water-mark x summary-bit combination of the tiered store.
    store_cfgs = [("device", None, None)] if "device" in stores else []
    if "tiered" in stores:
        store_cfgs += [
            ("tiered", hw, sb) for hw in high_waters for sb in summary_bits
        ]

    model = _build_model(model_name, n)
    from stateright_tpu.tensor import costmodel as cm

    configs = []

    def flush() -> list:
        """Rewrite the ranking JSON after EVERY config: a crashed config (or
        the driver's outer timeout) killing the sweep mid-way must not
        discard the configs that already measured."""
        measured = [c for c in configs if "states_per_sec" in c]
        ranking = sorted(
            measured, key=lambda c: c["states_per_sec"], reverse=True
        )
        result = {
            "workload": f"{model_name}-{n}",
            "table_log2": table_log2,
            "backend": jax.default_backend(),
            "model": {
                "lanes": model.lanes, "max_actions": model.max_actions,
            },
            "configs": configs,
            "ranking": [
                {
                    "layout": c["layout"],
                    "batch": c["batch"],
                    "store": c.get("store", "device"),
                    **(
                        {
                            "high_water": c["high_water"],
                            "summary_log2": c["summary_log2"],
                        }
                        if c.get("store") == "tiered"
                        else {}
                    ),
                    "states_per_sec": c["states_per_sec"],
                    "predicted_ms": round(c.get("predicted_ms", 0.0), 3),
                    "parity_ok": c["parity_ok"],
                }
                for c in ranking
            ],
        }
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)
        return ranking

    for batch in batches:
        for layout in variants:
            for store, hw, sb in store_cfgs:
                if store == "tiered" and LAYOUTS[layout][0] != "split":
                    continue  # tiered eviction is split-bucket-layout only
                tag = (
                    f" store=tiered(hw={hw},sb={sb})"
                    if store == "tiered"
                    else ""
                )
                print(
                    f"== {model_name}-{n} b={batch} layout={layout}{tag}",
                    flush=True,
                )
                rec = {
                    "workload": f"{model_name}-{n}",
                    "batch": batch,
                    "table_log2": table_log2,
                    "layout": layout,
                    "store": store,
                }
                cmd = [
                    sys.executable,
                    os.path.abspath(__file__),
                    model_name,
                    str(n),
                    str(batch),
                    str(table_log2),
                    str(repeats),
                    layout,
                ]
                if store == "tiered":
                    rec["high_water"] = hw
                    rec["summary_log2"] = sb
                    cmd += [store, str(hw), str(sb)]
                try:
                    proc = subprocess.run(
                        cmd,
                        capture_output=True,
                        text=True,
                        timeout=timeout,
                    )
                except subprocess.TimeoutExpired:
                    rec["error"] = f"timed out after {timeout:.0f}s"
                    configs.append(rec)
                    flush()
                    print("   TIMEOUT", flush=True)
                    continue
                sys.stderr.write(proc.stderr)
                line = next(
                    (
                        ln[len("RESULT_JSON "):]
                        for ln in proc.stdout.splitlines()
                        if ln.startswith("RESULT_JSON ")
                    ),
                    None,
                )
                if line is None:
                    tail = proc.stdout.strip().splitlines()
                    rec["error"] = (
                        tail[-1] if tail else f"rc={proc.returncode}"
                    )
                    configs.append(rec)
                    flush()
                    print(f"   FAILED: {rec['error']}", flush=True)
                    continue
                rec.update(json.loads(line))
                rec["predicted_ms"] = cm.step_cost(
                    model.lanes,
                    model.max_actions,
                    batch,
                    table_log2,
                    variant=cm.ENGINE_VARIANTS[LAYOUTS[layout]],
                    # Probe-only spill term: per-step eviction volume is
                    # workload-dependent and unknown pre-run; the measured
                    # spill_events in the RESULT_JSON calibrate it later.
                    spill={"summary_hashes": 4} if store == "tiered" else None,
                ).total_ms
                configs.append(rec)
                flush()
                print(
                    f"   {rec['states_per_sec']:,.0f}/s "
                    f"(predicted {rec['predicted_ms']:.2f} ms/step, "
                    f"parity_ok={rec['parity_ok']})",
                    flush=True,
                )

    ranking = flush()
    measured = [c for c in configs if "states_per_sec" in c]
    print(f"ranking written to {out_path}")
    if ranking:
        best = ranking[0]
        print(
            f"WINNER {best['layout']} b={best['batch']}: "
            f"{best['states_per_sec']:,.0f}/s"
        )
    # Parity failures or wholly-failed sweeps are errors.
    if not measured or not all(c["parity_ok"] for c in measured):
        return 1
    return 0


def run_sim_sweep(model_name, n, table_log2, traces_axis, dedup_axis,
                  sim_walks, sim_depth, repeats, timeout, out_path) -> int:
    """The fourth engine's chip-run command: race traces x dedup in
    subprocess-isolated single-config runs, join with the costmodel's
    committed walk-step predictions, rank by walks/s."""
    from stateright_tpu.knobs import SIM_DEDUP_KINDS

    bad = [d for d in dedup_axis if d not in SIM_DEDUP_KINDS]
    if bad:
        print(f"unknown dedup values {bad} ({' | '.join(SIM_DEDUP_KINDS)})")
        return 2
    model = _build_model(model_name, n)
    from stateright_tpu.tensor import costmodel as cm

    configs = []

    def flush() -> list:
        measured = [c for c in configs if "walks_per_sec" in c]
        ranking = sorted(
            measured, key=lambda c: c["walks_per_sec"], reverse=True
        )
        with open(out_path, "w") as f:
            json.dump(
                {
                    "workload": f"{model_name}-{n}",
                    "sim": True,
                    "table_log2": table_log2,
                    "backend": jax.default_backend(),
                    "model": {
                        "lanes": model.lanes,
                        "max_actions": model.max_actions,
                    },
                    "configs": configs,
                    "ranking": [
                        {
                            "traces": c["traces"],
                            "dedup": c["dedup"],
                            "walks_per_sec": c["walks_per_sec"],
                            "states_per_sec": c["states_per_sec"],
                            "lane_util": c["lane_util"],
                            "predicted_ms": round(
                                c.get("predicted_ms", 0.0), 3
                            ),
                        }
                        for c in ranking
                    ],
                },
                f,
                indent=1,
            )
        return ranking

    for traces in traces_axis:
        for dedup in dedup_axis:
            print(
                f"== {model_name}-{n} sim traces={traces} dedup={dedup}",
                flush=True,
            )
            rec = {
                "workload": f"{model_name}-{n}",
                "traces": traces,
                "dedup": dedup,
            }
            walks = sim_walks or str(4 * traces)
            cmd = [
                sys.executable, os.path.abspath(__file__),
                "sim", model_name, str(n), str(traces), dedup,
                str(walks), str(sim_depth), str(repeats), str(table_log2),
            ]
            try:
                proc = subprocess.run(
                    cmd, capture_output=True, text=True, timeout=timeout
                )
            except subprocess.TimeoutExpired:
                rec["error"] = f"timed out after {timeout:.0f}s"
                configs.append(rec)
                flush()
                print("   TIMEOUT", flush=True)
                continue
            sys.stderr.write(proc.stderr)
            line = next(
                (
                    ln[len("RESULT_JSON "):]
                    for ln in proc.stdout.splitlines()
                    if ln.startswith("RESULT_JSON ")
                ),
                None,
            )
            if line is None:
                tail = proc.stdout.strip().splitlines()
                rec["error"] = tail[-1] if tail else f"rc={proc.returncode}"
                configs.append(rec)
                flush()
                print(f"   FAILED: {rec['error']}", flush=True)
                continue
            rec.update(json.loads(line))
            rec["predicted_ms"] = cm.sim_step_cost(
                model.lanes, model.max_actions, traces,
                dedup=dedup, table_log2=table_log2,
            ).total_ms
            configs.append(rec)
            flush()
            print(
                f"   {rec['walks_per_sec']:,.0f} walks/s "
                f"(predicted {rec['predicted_ms']:.2f} ms/step, "
                f"lane_util={rec['lane_util']})",
                flush=True,
            )

    ranking = flush()
    print(f"ranking written to {out_path}")
    if ranking:
        best = ranking[0]
        print(
            f"WINNER sim traces={best['traces']} dedup={best['dedup']}: "
            f"{best['walks_per_sec']:,.0f} walks/s"
        )
    return 0 if ranking else 1


def _reeval_rankings(cm, stock_dev, fitted_dev) -> list:
    """Re-derive the two committed pre-hardware rankings under the fitted
    coefficients, next to the stock derivation. Returns the list of grid
    points whose winner flipped (empty = both committed defaults hold).

    r12: capped-vs-pallas insert on the paxos-3 geometry
    (lanes 21, max_actions 14) — committed call: capped stays the default
    at the r4 anchor (batch 3072, table 2^22); pallas wins small tables
    and huge batches. r18 (ROUND14/18 sim notes): shared-table sim dedup
    is priced as the same insert ops at batch=traces — committed call:
    trace-dedup stays the sim default; shared's insert term is under ~7%
    of the step until traces ~4k.
    """
    flips = []

    def w12(dev, table_log2, batch):
        capped = cm.step_cost(
            21, 14, batch, table_log2, variant="capped", device=dev
        ).total_ms
        pallas = cm.step_cost(
            21, 14, batch, table_log2, variant="pallas", device=dev
        ).total_ms
        return ("capped" if capped <= pallas else "pallas", capped, pallas)

    print("\nr12 capped-vs-pallas insert crossover (paxos-3, lanes 21 x "
          "acts 14) — stock | fitted:")
    grid = [(t, 3072) for t in (16, 18, 20, 22)]
    grid += [(22, 32768), (22, 131072)]
    for table_log2, batch in grid:
        s_win, s_c, s_p = w12(stock_dev, table_log2, batch)
        f_win, f_c, f_p = w12(fitted_dev, table_log2, batch)
        mark = ""
        if s_win != f_win:
            mark = "  <-- FLIP"
            flips.append(f"r12 table=2^{table_log2} batch={batch}: "
                         f"{s_win} -> {f_win}")
        print(f"  table=2^{table_log2:<2} batch={batch:<6} "
              f"stock: {s_win:<6} (capped {s_c:.2f} / pallas {s_p:.2f} ms)"
              f" | fitted: {f_win:<6} (capped {f_c:.2f} / pallas "
              f"{f_p:.2f} ms){mark}")
    s_anchor = w12(stock_dev, 22, 3072)[0]
    f_anchor = w12(fitted_dev, 22, 3072)[0]
    if s_anchor == f_anchor:
        print(f"  committed default at the r4 anchor holds: {f_anchor}")
    else:
        print(f"  COMMITTED DEFAULT FLIPS at the r4 anchor: "
              f"{s_anchor} -> {f_anchor}")

    def sim_row(dev, traces):
        tr = cm.sim_step_cost(21, 14, traces, dedup="trace", device=dev)
        sh = cm.sim_step_cost(
            21, 14, traces, dedup="shared", table_log2=22, device=dev
        )
        ins = sum(o.ms for o in sh.ops if o.name.startswith("insert"))
        return tr.total_ms, sh.total_ms, ins / max(sh.total_ms, 1e-12)

    print("\nr18 sim-walk shared-table overhead (paxos-3, table 2^22) — "
          "stock | fitted:")
    for traces in (1024, 2048, 4096, 8192):
        s_tr, s_sh, s_frac = sim_row(stock_dev, traces)
        f_tr, f_sh, f_frac = sim_row(fitted_dev, traces)
        s_win = "trace" if s_tr <= s_sh else "shared"
        f_win = "trace" if f_tr <= f_sh else "shared"
        mark = ""
        if s_win != f_win:
            mark = "  <-- FLIP"
            flips.append(f"r18 traces={traces}: {s_win} -> {f_win}")
        print(f"  traces={traces:<5} stock: trace {s_tr:.2f} / shared "
              f"{s_sh:.2f} ms, insert {100 * s_frac:.1f}% | fitted: "
              f"trace {f_tr:.2f} / shared {f_sh:.2f} ms, insert "
              f"{100 * f_frac:.1f}%{mark}")
    crossed = [t for t in (1024, 2048, 4096, 8192)
               if sim_row(fitted_dev, t)[2] > 0.07]
    if crossed:
        print(f"  fitted shared-insert term exceeds 7% of the step from "
              f"traces={crossed[0]} (committed call said ~4k)")
    else:
        print("  fitted shared-insert term stays under 7% across the grid")

    if flips:
        print("\nRANKING FLIPS under fitted coefficients:")
        for f in flips:
            print(f"  {f}")
    else:
        print("\nno committed ranking flips under fitted coefficients")
    return flips


def run_calibrate(argv: list) -> int:
    from stateright_tpu.obs.calib import (
        THETA_FIELDS,
        device_from_theta,
        fit_theta,
        holdout_eval,
        load_observations,
        overlay_dict,
    )
    from stateright_tpu.tensor import costmodel as cm

    def opt(name, default):
        if name in argv:
            return argv[argv.index(name) + 1]
        return default

    root = argv[0]
    only = opt("--device", None)
    ridge = float(opt("--ridge", 1e-2))
    out_arg = opt("--out", None)

    records = load_observations(root)
    if not records:
        print(f"no calibration records under {root} "
              f"(comparators flush to <root>/calib/)")
        return 1
    by_dev: dict = {}
    for rec in records:
        by_dev.setdefault(rec.get("device") or "tpu-v5e", []).append(rec)
    kinds = [only] if only else sorted(by_dev)
    rc = 0
    for kind in kinds:
        recs = by_dev.get(kind)
        if not recs:
            print(f"no records for device kind {kind!r} "
                  f"(have: {sorted(by_dev)})")
            rc = 1
            continue
        base = cm.stock_device(kind)
        theta, report = fit_theta(recs, base, ridge=ridge)
        n_rows = report["rows"]
        print(f"== {kind}: {len(recs)} record(s), {n_rows} observation "
              f"row(s) ==")
        print(f"  median |drift-1|: stock "
              f"{report['median_abs_drift_stock']:.4f} -> fitted "
              f"{report['median_abs_drift_fitted']:.4f}")
        fitted_dev = device_from_theta(base, theta)
        print("  coefficient rates (stock -> fitted):")
        for name, field, _kind in THETA_FIELDS:
            print(f"    {field:<16} {getattr(base, field):>12.4g} -> "
                  f"{getattr(fitted_dev, field):>12.4g}")
        holdout = holdout_eval(recs, base, ridge=ridge)
        if holdout:
            print("  leave-one-key-out holdout (median |drift-1|):")
            for key, h in sorted(holdout.items()):
                verdict = "better" if h["fitted"] < h["stock"] else "WORSE"
                print(f"    {key}: stock {h['stock']:.4f} -> fitted "
                      f"{h['fitted']:.4f} ({verdict})")

        overlay = overlay_dict(base, theta, report)
        out_path = out_arg or f"calib-overlay-{kind}.json"
        try:
            with open(out_path, "w") as f:
                json.dump(overlay, f, indent=2)
            print(f"  overlay written to {out_path}; activate with "
                  f"SR_TPU_COSTMODEL_CALIB={out_path}")
        except OSError as e:
            print(f"  overlay write failed: {e}")
            rc = 1

        _reeval_rankings(cm, base, fitted_dev)
    return rc


def main() -> int:
    argv = sys.argv[1:]
    if argv and argv[0] == "sim":
        if len(argv) < 5:
            print(__doc__)
            return 2
        return run_sim_single(
            argv[1], int(argv[2]), int(argv[3]), argv[4],
            int(argv[5]) if len(argv) > 5 else None,
            int(argv[6]) if len(argv) > 6 else 256,
            max(1, int(argv[7])) if len(argv) > 7 else 3,
            int(argv[8]) if len(argv) > 8 else 20,
        )
    if argv and argv[0] == "--calibrate":
        if len(argv) < 2:
            print(__doc__)
            return 2
        return run_calibrate(argv[1:])
    if argv and argv[0] == "--sweep":
        if len(argv) < 4:
            print(__doc__)
            return 2
        return run_sweep(argv[1:])
    if len(argv) < 4:
        print(__doc__)
        return 2
    model_name, n, batch, table_log2 = (
        argv[0], int(argv[1]), int(argv[2]), int(argv[3])
    )
    repeats = max(1, int(argv[4])) if len(argv) > 4 else 3
    layout = argv[5] if len(argv) > 5 else "split"
    store = argv[6] if len(argv) > 6 else "device"
    high_water = float(argv[7]) if len(argv) > 7 else 0.85
    summary_log2 = int(argv[8]) if len(argv) > 8 else 20
    return run_single(
        model_name, n, batch, table_log2, repeats, layout,
        store=store, high_water=high_water, summary_log2=summary_log2,
    )


if __name__ == "__main__":
    sys.exit(main())
