"""Benchmark: device whole-search checker vs the compiled CPU baseline on the
BASELINE.json metric workloads — Paxos-3 (north star) and 2PC-4 — plus the
reference's 2-client Paxos golden config as the parity anchor.

Baseline: this image has no cargo/rustc, so the reference's multithreaded Rust
`BfsChecker` (the thing BASELINE.md says to measure via bench.sh) is
approximated by `stateright_tpu/_native/baseline_bfs.cpp` — a C++ port of the
same search over the same state spaces, validated against the reference's
golden counts (2pc-3=288, 2pc-5=8,832, paxos-2=16,668 — examples/2pc.rs:153-159,
examples/paxos.rs:327). It packs states into u32 lanes, so it does *less* work
per state than the Rust checker's boxed states: a conservative baseline.

Robustness contract: exactly ONE JSON line is printed on stdout no matter
what. The device is probed once with a trivial jitted op before any search
kernel compiles; if the device is unusable the line
carries `value: null, vs_baseline: null` plus a `device_error` field (the CPU
baseline stays in detail.cpu_baseline) instead of dying with rc=1 and no
output. Count-parity failures are reported in an `error`
field (never a bare `assert`, which `python -O` would strip).
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time
import traceback

from stateright_tpu.compile_cache import use_compile_cache

# Golden counts (generated, unique): reference examples/paxos.rs:327 for
# paxos-2; the rest were computed by the compiled baseline checker and
# cross-validated against the device engines and the host checkers
# (increment_lock sym golden is host-DFS-sym
# cross-validated in tests/test_tensor_symmetry.py). Lowered workloads
# (abd-ordered, paxos5s4c) carry NO pinned golden: the exact-closure host
# traversal computes the oracle at build time and the worker asserts against
# it (closure_stats).
GOLDEN = {
    ("paxos", 2): (32_971, 16_668),
    ("paxos", 3): (2_420_477, 1_194_428),
    ("2pc", 4): (8_258, 1_568),
    ("2pc", 10): (817_760_258, 61_515_776),
    ("inclock", 6): (7_825, 7_825),
    ("increment_lock", 6): (7_825, 7_825),  # C++ baseline name for the same
    ("inclock-sym", 6): (40, 25),
}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- compiled CPU baseline -----------------------------------------------------


def compile_baseline() -> str | None:
    try:
        from stateright_tpu._native import build

        return build("baseline_bfs", exe=True)
    except Exception as e:  # noqa: BLE001 — baseline is best-effort
        log(f"baseline compile failed: {e}")
        return None


def run_baseline(exe: str, model: str, n: int, repeats: int = 3,
                 threads: int | None = None):
    """Best-of-N run of the compiled checker. Returns dict or None; keeps the
    best run that *succeeded* even if later repeats fail. `threads` pins the
    checker's thread count (baseline_bfs.cpp argv[3]); None lets it default
    to hardware_concurrency."""
    cmd = [exe, model, str(n)]
    if threads is not None:
        cmd.append(str(threads))
    best = None
    for _ in range(repeats):
        try:
            proc = subprocess.run(
                cmd,
                check=True,
                capture_output=True,
                text=True,
                timeout=1800,
            )
        except Exception as e:  # noqa: BLE001
            log(f"baseline run {model}-{n} failed: {e}")
            continue
        m = re.search(
            r"states=(\d+) unique=(\d+) depth=(\d+) sec=([\d.]+) threads=(\d+) "
            r"violations=(\d+)",
            proc.stdout,
        )
        if not m:
            log(f"baseline output unparseable: {proc.stdout!r}")
            continue
        r = {
            "states": int(m.group(1)),
            "unique": int(m.group(2)),
            "depth": int(m.group(3)),
            "sec": float(m.group(4)),
            "threads": int(m.group(5)),
            "violations": int(m.group(6)),
        }
        if best is None or r["sec"] < best["sec"]:
            best = r
    if best:
        best["states_per_sec"] = best["states"] / max(best["sec"], 1e-9)
    return best


# -- device ----------------------------------------------------------------


# The parent process never imports JAX: a chip belongs to one process at a
# time, so the probe and every workload run in their own subprocess, one
# after another.
_PROBE_SNIPPET = (
    "import jax, jax.numpy as jnp;"
    "jax.jit(lambda a: a * 2 + 1)(jnp.arange(8)).block_until_ready();"
    "d = jax.devices();"
    "print('PROBE_OK', d[0].platform, repr(d[0].device_kind), len(d))"
)


def probe_device(timeout: float = 300.0):
    """Run a trivial jitted op on the default backend in a subprocess;
    returns (ok, error). One attempt: a failed probe fails the device part
    of the run."""
    try:
        proc = subprocess.run(
            [sys.executable, "-c", _PROBE_SNIPPET],
            capture_output=True,
            text=True,
            timeout=timeout,
        )
    except (subprocess.TimeoutExpired, OSError) as e:
        return False, f"probe subprocess failed: {e}"
    if proc.returncode == 0 and "PROBE_OK" in proc.stdout:
        log(f"device probe ok: {proc.stdout.strip()}")
        return True, ""
    tail = (proc.stderr or proc.stdout).strip().splitlines()
    return False, tail[-1] if tail else f"rc={proc.returncode}"


def device_search_subprocess(
    model_name: str,
    n: int,
    timeout: float = 1500.0,
    mode: str = "--worker",
    env_extra: dict | None = None,
):
    """Run one device workload in a FRESH subprocess (`bench.py --worker`).

    A workload that hangs (e.g. a pathological compile) is bounded by
    `timeout` instead of eating the whole bench, and a crashed workload
    cannot poison the backend state of the remaining ones. Workloads run
    strictly one after another: the chip belongs to one process at a time.

    Returns (result dict | None, error str | None).
    """
    env = None
    if env_extra:
        env = dict(os.environ)
        env.update(env_extra)
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), mode, model_name, str(n)],
            capture_output=True,
            text=True,
            timeout=timeout,
            env=env,
        )
    except subprocess.TimeoutExpired as e:
        # Keep the partial stderr so the hung phase is attributable.
        if e.stderr:
            err_text = e.stderr if isinstance(e.stderr, str) else e.stderr.decode(errors="replace")
            sys.stderr.write(err_text)
        return None, f"workload timed out after {timeout:.0f}s and was killed"
    except Exception as e:  # noqa: BLE001
        return None, f"worker spawn failed: {e}"
    sys.stderr.write(proc.stderr)
    line = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else ""
    if not line.startswith("{"):
        tail = (proc.stderr or proc.stdout).strip().splitlines()
        return None, tail[-1] if tail else f"worker rc={proc.returncode}"
    try:
        payload = json.loads(line)
    except ValueError:
        return None, f"unparseable worker output: {line[:200]!r}"
    return payload.get("result"), payload.get("error")


def _abd_ordered_lowered(depth: int):
    """ABD linearizable register, 2 clients / 3 servers, ORDERED network
    (BASELINE.json config #3; ref examples/linearizable-register.rs,
    bench.sh:31-33), via the exact-closure generic lowering bounded at
    `depth` (the full ordered space is not host-enumerable)."""
    from stateright_tpu.actor import Network
    from stateright_tpu.examples.abd import AbdModelCfg
    from stateright_tpu.tensor.lowering import lower_actor_model

    cfg = AbdModelCfg(2, 3, network=Network.new_ordered())
    return lower_actor_model(
        cfg.into_model(),
        closure="exact",
        closure_max_depth=depth,
        max_joint_states=1 << 22,
    )


def _paxos5s4c_lowered(depth: int):
    """Paxos 5 servers / 4 clients deep BFS (BASELINE.json config #5) via
    the exact-closure generic lowering bounded at `depth`."""
    from stateright_tpu.actor import Network
    from stateright_tpu.examples.paxos import PaxosModelCfg
    from stateright_tpu.tensor.lowering import lower_actor_model

    cfg = PaxosModelCfg(
        client_count=4,
        server_count=5,
        network=Network.new_unordered_nonduplicating(),
    )
    return lower_actor_model(
        cfg.into_model(),
        closure="exact",
        closure_max_depth=depth,
        max_joint_states=1 << 22,
        max_emit=6,
        # pool_size: auto-sized by the exact closure to the PROVEN max
        # occupancy (18 at depth 10 — round 5; the explicit 24 it replaces
        # cost 6 dead lanes AND 6 dead deliver slots per state).
    )


def _build_workload(model_name: str, n: int):
    """-> (model, batch, table_log2, run_kwargs, engine_kwargs, golden
    (gen, unique) or None, closure_sec). Lowered workloads compute their
    own oracle (closure_stats) during the host closure."""
    t0 = time.monotonic()
    engine_kwargs: dict = {}
    import jax

    # The two backends want opposite batch sizes (round-4 v5e sweep vs CPU
    # sweep): on TPU step cost is near-linear in batch
    # while the frontier is often sub-batch, so small batches win — final
    # v5e bracket at session-end kernels: 627k/s @3072 vs 616k @4096,
    # 599k @2560, 572k @6144, 280k @32768; the 1-core CPU backend
    # amortizes per-step overhead with big batches (101k @32768 vs 53k
    # @65536, smaller is worse). Key off the EFFECTIVE backend so CPU
    # rehearsals stay comparable round over round.
    on_cpu = jax.default_backend() == "cpu"
    if model_name == "paxos":
        from stateright_tpu.tensor.paxos import TensorPaxos

        model = TensorPaxos(client_count=n)
        big = (32768, 22) if on_cpu else (3072, 22)
        batch, table_log2 = (2048, 16) if n <= 2 else big
        run_kwargs, golden = {}, GOLDEN[(model_name, n)]
    elif model_name == "2pc":
        from stateright_tpu.tensor.models import TensorTwoPhaseSys

        model = TensorTwoPhaseSys(n)
        # 2pc-10: batch 32768 amortizes the per-step table/queue traffic 4x
        # vs 8192, and donated chunk dispatches avoid the multi-GB carry
        # copy if the run is ever chunked (round-4 CPU A/B: ~140k gen/s
        # sustained, full space in ~100 min on one core).
        # queue_log2=26 right-sizes the frontier queue (61.5 M uniques
        # < 2^26): at table 2^27 a table-sized queue alone is 9.1 GB and
        # the workload crashed the 16 GB v5e worker mid-run.
        batch, table_log2 = (512, 14) if n < 8 else (32768, 27)
        run_kwargs = {}
        if n >= 8:
            engine_kwargs["donate_chunks"] = True
            engine_kwargs["queue_log2"] = 26
            # Chunked dispatches (donated, so near-free): ~64-step
            # dispatches instead of ONE multi-minute device program
            # (ROADMAP A1 questions whether the chunking still pays).
            run_kwargs["budget"] = 64
        golden = GOLDEN[(model_name, n)]
    elif model_name in ("inclock", "inclock-sym"):
        from stateright_tpu.tensor.models import TensorIncrementLock

        model = TensorIncrementLock(n, symmetry=model_name == "inclock-sym")
        batch, table_log2 = (1024, 14) if model_name == "inclock" else (512, 10)
        run_kwargs, golden = {}, GOLDEN[(model_name, n)]
    elif model_name == "abd-ordered":
        model = _abd_ordered_lowered(depth=n)
        batch, table_log2 = 2048, 16
        run_kwargs = {"target_max_depth": n}
        s = model.closure_stats
        golden = (s["generated"], s["unique"])
    elif model_name == "paxos5s4c":
        model = _paxos5s4c_lowered(depth=n)
        batch, table_log2 = 4096, 19
        run_kwargs = {"target_max_depth": n}
        s = model.closure_stats
        golden = (s["generated"], s["unique"])
    else:
        raise ValueError(f"unknown workload {model_name!r}")
    # BENCH_STORE=tiered: race the two-tier state store (device hot set +
    # host spill tier) on every workload; BENCH_HIGH_WATER /
    # BENCH_SUMMARY_LOG2 tune it. Malformed values fall back to the store
    # defaults — an observability knob must never kill the bench — but an
    # unknown store NAME is called out loudly: a typo'd value silently
    # benching the device store would cost a chip run exactly the spill
    # rows the env var exists for (same policy as unknown bench flags).
    bench_store = os.environ.get("BENCH_STORE", "")
    if bench_store and bench_store not in ("device", "tiered"):
        log(f"unknown BENCH_STORE {bench_store!r} ignored "
            "(known: device | tiered)")
    if bench_store == "tiered":
        engine_kwargs["store"] = "tiered"
        try:
            engine_kwargs["high_water"] = float(
                os.environ.get("BENCH_HIGH_WATER", "0.85")
            )
        except ValueError:
            pass
        try:
            engine_kwargs["summary_log2"] = int(
                os.environ.get("BENCH_SUMMARY_LOG2", "20")
            )
        except ValueError:
            pass
    return (
        model, batch, table_log2, run_kwargs, engine_kwargs, golden,
        time.monotonic() - t0,
    )


def _parity_err(model_name, n, result, golden):
    if golden is None:
        return None
    if (result.state_count, result.unique_state_count) != golden:
        return (
            f"{model_name}-{n} parity failure: device "
            f"(gen={result.state_count}, "
            f"unique={result.unique_state_count}) != "
            f"golden (gen={golden[0]}, unique={golden[1]})"
        )
    return None


def _time_search(search, run_kwargs, repeats: int, closure_s: float):
    """Shared timing protocol: one compile/warm-up run, then best-of-N.

    Chunked runs (a `budget` in run_kwargs) keep a carry across `run()`
    calls — without a reset, a completed search would make every repeat a
    no-op resume reporting near-zero duration (the 2pc-10 worker once
    "measured" 12 billion states/s that way). Fresh-start every repeat;
    whole-search engines ignore the reset."""
    t0 = time.monotonic()
    warm = search.run(**run_kwargs)  # compile + warm-up
    compile_s = time.monotonic() - t0
    # Long workloads get best-of-1: a ~15-min search repeated 3x would blow
    # the per-workload subprocess timeout for no extra signal.
    if warm.duration > 120:
        repeats = 1
    best = None
    for _ in range(repeats):
        if hasattr(search, "reset"):
            search.reset()
        r = search.run(**run_kwargs)
        if best is None or r.duration < best.duration:
            best = r
    out = {
        "states": best.state_count,
        "unique": best.unique_state_count,
        "sec": round(best.duration, 4),
        "states_per_sec": best.state_count / max(best.duration, 1e-9),
        "compile_sec": round(compile_s, 1),
    }
    if closure_s > 1.0:
        out["closure_sec"] = round(closure_s, 1)
    return best, out


def _attach_roofline(out: dict, best, model, batch: int, table_log2: int,
                     search) -> None:
    """Cost-model utilization fields: bytes touched per
    generated state from tensor/costmodel.py, and the effective-HBM
    fraction (the MFU analogue) when the run was on real accelerator HBM.
    CPU-backend rehearsals get the byte count as `cpu_bytes_per_state`
    instead — the model's CPU *times* are low-confidence, its bytes exact.
    """
    try:
        import jax

        from stateright_tpu.tensor import costmodel as cm

        layout = getattr(search, "table_layout", "split")
        insert_variant = getattr(search, "insert_variant", "sort")
        variant = cm.ENGINE_VARIANTS.get((layout, insert_variant), "split")
        states_per_step = best.state_count / max(best.steps, 1)
        # new_frac: populated-lane fraction of B = generated-per-step over
        # the flat successor lane count — what the capped path tiles over.
        B = batch * model.max_actions
        new_frac = min(states_per_step / B, 1.0)
        bps = cm.bytes_per_state(
            model.lanes, model.max_actions, batch, table_log2,
            states_per_step,
            variant=variant,
            append=getattr(search, "append", "dus"),
            new_frac=new_frac,
        )
        out["bytes_per_state"] = round(bps, 1)
        if jax.default_backend() == "cpu":
            out["cpu_bytes_per_state"] = out["bytes_per_state"]
        else:
            # Peaks of the device this ran on; an unlisted kind raises.
            peaks = cm.spec_for_device_kind(jax.devices()[0].device_kind)
            out["hbm_frac"] = round(
                cm.hbm_frac(out["states_per_sec"], bps, peaks), 5
            )
    except Exception as e:  # noqa: BLE001 — reporting must never kill a run
        log(f"roofline annotation failed: {e}")


def device_search(model_name: str, n: int, repeats: int = 3):
    """Run the resident engine; returns (result dict, parity error or None)."""
    use_compile_cache()
    from stateright_tpu.tensor.resident import ResidentSearch

    model, batch, table_log2, run_kwargs, engine_kwargs, golden, closure_s = (
        _build_workload(model_name, n)
    )
    search = ResidentSearch(
        model, batch_size=batch, table_log2=table_log2, **engine_kwargs
    )
    best, out = _time_search(search, run_kwargs, repeats, closure_s)
    _attach_roofline(out, best, model, batch, table_log2, search)
    _attach_store_stats(out, search)
    _attach_telemetry(out, best)
    return out, _parity_err(model_name, n, best, golden)


def _attach_telemetry(out: dict, best) -> None:
    """Step-telemetry digest (obs/ring.py) in the bench row — lane
    utilization, fill trajectory, step-time percentiles ride in
    detail.device so every BENCH_r*.json can answer "where did the step
    budget go" without a rerun."""
    try:
        if best.detail and "telemetry" in best.detail:
            out["telemetry"] = best.detail["telemetry"]
    except Exception as e:  # noqa: BLE001 — reporting must never kill a run
        log(f"telemetry annotation failed: {e}")


def device_search_obs(model_name: str, n: int):
    """BENCH_OBS=1 row: the r4 anchor workload run twice on the resident
    engine — telemetry OFF then telemetry ON — proving the ring buffer's
    overhead on the pinned row (acceptance: <= 2% step time; the ring adds
    no per-step host sync, so the delta is one ~32-byte in-loop scatter).
    Returns (result dict for the telemetry-ON run plus `sec_off` and
    `telemetry_overhead_pct`, parity error or None)."""
    use_compile_cache()
    from stateright_tpu.tensor.resident import ResidentSearch

    model, batch, table_log2, run_kwargs, engine_kwargs, golden, closure_s = (
        _build_workload(model_name, n)
    )
    runs = {}
    for telemetry in (False, True):
        search = ResidentSearch(
            model, batch_size=batch, table_log2=table_log2,
            telemetry=telemetry, **engine_kwargs,
        )
        best, out = _time_search(search, run_kwargs, repeats=2,
                                 closure_s=closure_s)
        runs[telemetry] = (best, out)
    best_on, out = runs[True]
    _attach_telemetry(out, best_on)
    sec_off = runs[False][1]["sec"]
    out["sec_off"] = sec_off
    out["telemetry_overhead_pct"] = round(
        100.0 * (out["sec"] - sec_off) / max(sec_off, 1e-9), 2
    )
    perr = _parity_err(model_name, n, best_on, golden) or _parity_err(
        model_name, n, runs[False][0], golden
    )
    return out, perr


def device_search_calib(model_name: str, n: int):
    """BENCH_CALIB=1 row: the 2pc-4 anchor run twice on the resident
    engine — calibration comparator OFF (SR_TPU_CALIB=0) then ON —
    proving the measured-vs-predicted join's overhead on the pinned row
    (host arithmetic at chunk granularity, no device work; acceptance:
    within noise). The ON run's `detail.calib` digest (predicted vs
    measured ms, drift ratio, per-term attribution) rides in the row."""
    use_compile_cache()
    from stateright_tpu.tensor.resident import ResidentSearch

    model, batch, table_log2, run_kwargs, engine_kwargs, golden, closure_s = (
        _build_workload(model_name, n)
    )
    runs = {}
    try:
        for enabled in (False, True):
            os.environ["SR_TPU_CALIB"] = "1" if enabled else "0"
            search = ResidentSearch(
                model, batch_size=batch, table_log2=table_log2,
                telemetry=True, **engine_kwargs,
            )
            best, out = _time_search(search, run_kwargs, repeats=2,
                                     closure_s=closure_s)
            runs[enabled] = (best, out)
    finally:
        os.environ.pop("SR_TPU_CALIB", None)
    best_on, out = runs[True]
    _attach_telemetry(out, best_on)
    if best_on.detail and "calib" in best_on.detail:
        out["calib"] = best_on.detail["calib"]
    sec_off = runs[False][1]["sec"]
    out["sec_off"] = sec_off
    out["calib_overhead_pct"] = round(
        100.0 * (out["sec"] - sec_off) / max(sec_off, 1e-9), 2
    )
    perr = _parity_err(model_name, n, best_on, golden) or _parity_err(
        model_name, n, runs[False][0], golden
    )
    return out, perr


def device_search_journal(model_name: str, n: int):
    """BENCH_OBS=1 journal sub-row: the anchor workload through a
    foreground CheckService twice — flight recorder OFF then ON
    (events_out= JSONL journal, obs/events.py) — pricing the journal's
    per-step emit + bounded-flush cost on the service path where it
    actually runs (acceptance: <= 5%, expected within noise: one dict +
    one buffered JSON line per fused step and per job transition).
    Cold-vs-cold like the faults row: each side builds a fresh service
    (fresh jit closures), best-of-2. Returns (result dict for the
    journal-ON run plus `sec_journal_off`, `journal_overhead_pct`, and
    the recorded `journal_events` count, parity error or None)."""
    use_compile_cache()
    import tempfile

    from stateright_tpu.obs.events import read_journal
    from stateright_tpu.service import CheckService

    model, batch, table_log2, run_kwargs, engine_kwargs, golden, closure_s = (
        _build_workload(model_name, n)
    )
    svc_kw = {
        k: v for k, v in engine_kwargs.items()
        if k in ("store", "high_water", "summary_log2")
    }
    runs = {}
    journal_events = 0
    with tempfile.TemporaryDirectory(prefix="srtpu-bench-journal-") as td:
        for journal in (False, True):
            best, best_sec = None, None
            for rep in range(2):
                jpath = os.path.join(td, f"j{rep}.jsonl")
                extra = {"events_out": jpath} if journal else {}
                svc = CheckService(
                    batch_size=batch, table_log2=table_log2,
                    background=False, **svc_kw, **extra,
                )
                try:
                    t0 = time.monotonic()
                    h = svc.submit(model, **{
                        k: v for k, v in run_kwargs.items()
                        if k in ("target_state_count", "target_max_depth")
                    })
                    svc.drain()
                    r = h.result()
                    sec = time.monotonic() - t0
                finally:
                    svc.close()
                if best_sec is None or sec < best_sec:
                    best, best_sec = r, sec
                if journal:
                    journal_events = max(
                        journal_events, len(read_journal(jpath))
                    )
            runs[journal] = (best, best_sec)
    best_on, sec_on = runs[True]
    sec_off = runs[False][1]
    out = {
        "states": best_on.state_count,
        "unique": best_on.unique_state_count,
        "sec": round(sec_on, 4),
        "states_per_sec": best_on.state_count / max(sec_on, 1e-9),
        "sec_journal_off": round(sec_off, 4),
        "journal_overhead_pct": round(
            100.0 * (sec_on - sec_off) / max(sec_off, 1e-9), 2
        ),
        "journal_events": journal_events,
    }
    perr = _parity_err(model_name, n, best_on, golden) or _parity_err(
        model_name, n, runs[False][0], golden
    )
    return out, perr


def device_search_pallas(model_name: str, n: int):
    """BENCH_PALLAS=1 row: the anchor workload run twice on the resident
    engine — insert_variant="capped" (the r6 winner) then "pallas" (the
    SURVEY §7 end-state kernel, ROADMAP item 2) — the insert-design A/B.
    On CPU images the kernel runs under Pallas interpret mode, so this
    number prices plumbing and parity, not the silicon bet; the committed
    pre-hardware ranking lives in tensor/costmodel.py (predict_ranking).
    Returns (result dict for the PALLAS run plus
    `sec_capped` and the `pallas_vs_capped` speed ratio, parity error or
    None)."""
    use_compile_cache()
    from stateright_tpu.tensor.resident import ResidentSearch

    model, batch, table_log2, run_kwargs, engine_kwargs, golden, closure_s = (
        _build_workload(model_name, n)
    )
    runs = {}
    search_p = None
    for variant in ("capped", "pallas"):
        search = ResidentSearch(
            model, batch_size=batch, table_log2=table_log2,
            insert_variant=variant, **engine_kwargs,
        )
        best, out = _time_search(search, run_kwargs, repeats=2,
                                 closure_s=closure_s)
        runs[variant] = (best, out)
        if variant == "pallas":
            search_p = search
        # The capped engine's table/queue buffers are dropped here, before
        # the pallas engine is built — keeping both alive would double
        # device memory pressure during the timed run at anchor sizes.
        del search
    best_p, out = runs["pallas"]
    _attach_roofline(out, best_p, model, batch, table_log2, search_p)
    sec_capped = runs["capped"][1]["sec"]
    out["sec_capped"] = sec_capped
    # >1 = pallas beats capped on this backend/workload.
    out["pallas_vs_capped"] = round(sec_capped / max(out["sec"], 1e-9), 3)
    perr = _parity_err(model_name, n, best_p, golden) or _parity_err(
        model_name, n, runs["capped"][0], golden
    )
    return out, perr


def device_search_faults(model_name: str, n: int):
    """BENCH_FAULTS=1 row: the anchor workload run twice — plain resident
    engine vs `run_supervised` with injection DISABLED — proving the
    supervisor's overhead (run slicing + periodic atomic checkpoints +
    watchdog plumbing) is within noise when nothing faults. Returns (result
    dict for the SUPERVISED run plus `sec_unsupervised`,
    `supervisor_overhead_pct`, and the `faults` recovery digest, parity
    error or None)."""
    use_compile_cache()
    import os
    import shutil
    import tempfile

    from stateright_tpu.faults import FaultPlan, SupervisorConfig, run_supervised
    from stateright_tpu.tensor.resident import ResidentSearch

    model, batch, table_log2, run_kwargs, engine_kwargs, golden, closure_s = (
        _build_workload(model_name, n)
    )
    # Cold-vs-cold A/B: `run_supervised` necessarily builds a fresh engine
    # (per-instance jit closures recompile), so the plain side is timed the
    # same way — fresh instance, end-to-end including compile — or the
    # "overhead" would mostly be the compile asymmetry.
    plain_best = None
    plain_sec = None
    for _ in range(2):
        search = ResidentSearch(
            model, batch_size=batch, table_log2=table_log2, **engine_kwargs
        )
        t0 = time.monotonic()
        r = search.run(**run_kwargs)
        sec = time.monotonic() - t0 - closure_s
        if plain_sec is None or sec < plain_sec:
            plain_best, plain_sec = r, sec

    cfg = SupervisorConfig(checkpoint_every_steps=512)
    sup = None
    best_sec = None
    for rep in range(2):  # same best-of-N protocol as the plain run
        # Fresh checkpoint dir per rep: reusing one path would make rep 2
        # restore rep 1's FINAL generation and time a vacuous resume.
        ckpt_dir = tempfile.mkdtemp(prefix="bench_faults_")
        try:
            t0 = time.monotonic()
            sup = run_supervised(
                model,
                engine="resident",
                # Injection disabled: an EMPTY plan, not None — None falls
                # back to SR_TPU_FAULTS, and a leftover chaos env var must
                # not contaminate the overhead measurement.
                plan=FaultPlan(),
                config=cfg,
                checkpoint_path=os.path.join(ckpt_dir, "bench.ckpt.npz"),
                engine_kwargs=dict(
                    batch_size=batch, table_log2=table_log2, **engine_kwargs
                ),
                run_kwargs=run_kwargs,
            )
            sec = time.monotonic() - t0 - closure_s
        finally:
            shutil.rmtree(ckpt_dir, ignore_errors=True)
        if best_sec is None or sec < best_sec:
            best_sec = sec

    out = {
        "states": sup.state_count,
        "unique": sup.unique_state_count,
        "sec": round(best_sec, 4),
        "states_per_sec": sup.state_count / max(best_sec, 1e-9),
        "sec_unsupervised": round(plain_sec, 4),
        "supervisor_overhead_pct": round(
            100.0 * (best_sec - plain_sec) / max(plain_sec, 1e-9), 2
        ),
        "faults": sup.detail.get("faults", {}),
    }
    perr = _parity_err(model_name, n, sup, golden) or _parity_err(
        model_name, n, plain_best, golden
    )
    return out, perr


def _attach_store_stats(out: dict, search) -> None:
    """Per-tier occupancy counters in every artifact of a tiered run (the
    DEVICE_DETAIL_FIELDS tail); no-op on the plain device store."""
    try:
        stats = getattr(search, "store_stats", lambda: None)()
        if stats:
            for f in ("hot_fill", "spilled_states", "spill_events"):
                out[f] = stats[f]
    except Exception as e:  # noqa: BLE001 — reporting must never kill a run
        log(f"store-stats annotation failed: {e}")


def device_search_service(n_jobs: int = 8):
    """BENCH_SERVICE=1 row: throughput of a mixed job batch through the
    multi-job check service (one shared device table, continuous batching)
    vs the SAME jobs run serially on fresh standalone engines — the
    serving-layer A/B. Composition: 3x 2pc-3, 3x 2pc-4, 2x inclock-6.
    Returns (result dict, parity error or None); parity = every service
    job's counts equal its serial twin's."""
    use_compile_cache()
    from stateright_tpu.service import CheckService
    from stateright_tpu.tensor.frontier import FrontierSearch
    from stateright_tpu.tensor.models import (
        TensorIncrementLock,
        TensorTwoPhaseSys,
    )

    m3, m4, mi = (
        TensorTwoPhaseSys(3), TensorTwoPhaseSys(4), TensorIncrementLock(6)
    )
    jobs = ([m3] * 3 + [m4] * 3 + [mi] * 2)[:n_jobs]

    # Serial reference: a fresh standalone engine per job — the deployment
    # story the service replaces (each engine compiles its own step and
    # owns the whole table for its run).
    t0 = time.monotonic()
    serial = []
    serial_steps = 0
    for m in jobs:
        fs = FrontierSearch(m, batch_size=1024, table_log2=16)
        r = fs.run()
        serial.append(r)
        serial_steps += r.steps
    serial_sec = time.monotonic() - t0

    t0 = time.monotonic()
    svc = CheckService(batch_size=1024, table_log2=18, background=False)
    handles = [svc.submit(m) for m in jobs]
    svc.drain()
    service_sec = time.monotonic() - t0
    results = [h.result() for h in handles]
    service_steps = svc.stats()["device_steps"]
    svc.close()

    err = None
    for i, (r, s) in enumerate(zip(results, serial)):
        got = (r.state_count, r.unique_state_count, r.max_depth)
        want = (s.state_count, s.unique_state_count, s.max_depth)
        # Full items comparison: the discovery FINGERPRINTS must survive
        # the salting round-trip bit-identically, not just the names.
        if got != want or sorted(r.discoveries.items()) != sorted(
            s.discoveries.items()
        ):
            err = (
                f"service parity failure on job {i}: {got} / "
                f"{sorted(r.discoveries.items())} != serial {want} / "
                f"{sorted(s.discoveries.items())}"
            )
            break
    states = sum(r.state_count for r in results)
    out = {
        "states": states,
        "unique": sum(r.unique_state_count for r in results),
        "sec": round(service_sec, 4),
        "states_per_sec": states / max(service_sec, 1e-9),
        "compile_sec": 0.0,  # compiles are inside both wall clocks (A/B fair)
        "n_jobs": len(jobs),
        "jobs_per_sec": round(len(jobs) / max(service_sec, 1e-9), 4),
        "serial_sec": round(serial_sec, 4),
        "vs_serial": round(serial_sec / max(service_sec, 1e-9), 3),
        "service_steps": service_steps,
        "serial_steps": serial_steps,
    }
    return out, err


def device_search_fleet(n_replicas: int = 3):
    """BENCH_FLEET=1 row: the mixed job set through an N-replica service
    fleet (consistent-hash router, work stealing) vs the SAME jobs through
    a 1-replica fleet — the scale-out A/B the ROADMAP item 1 acceptance
    names. Reports jobs/s, the N-vs-1 ratio, and the p50/p99 submit→result
    latency of the fleet run. Composition: 3x 2pc-3, 3x 2pc-4, 2x
    inclock-4. Parity = every fleet job's counts and discovery
    fingerprints equal its 1-replica twin's (bit-identical scale-out)."""
    use_compile_cache()
    from stateright_tpu.service import ServiceFleet
    from stateright_tpu.tensor.models import (
        TensorIncrementLock,
        TensorTwoPhaseSys,
    )

    m3, m4, mi = (
        TensorTwoPhaseSys(3), TensorTwoPhaseSys(4), TensorIncrementLock(4)
    )
    jobs = [m3] * 3 + [m4] * 3 + [mi] * 2

    def run_fleet(n):
        fleet = ServiceFleet(
            n_replicas=n,
            background=True,
            max_resident=2,
            service_kwargs=dict(batch_size=1024, table_log2=17),
        )
        t0 = time.monotonic()
        handles = [fleet.submit(m) for m in jobs]
        fleet.drain(timeout=1800)
        sec = time.monotonic() - t0
        results = [h.result() for h in handles]
        lat_ms = sorted(
            (h._job.finished_at - h._job.submitted_at) * 1000.0
            for h in handles
        )
        stats = fleet.stats()
        fleet.close()
        return sec, results, lat_ms, stats

    one_sec, one_results, _one_lat, _ = run_fleet(1)
    sec, results, lat_ms, stats = run_fleet(n_replicas)

    err = None
    for i, (r, s) in enumerate(zip(results, one_results)):
        got = (r.state_count, r.unique_state_count, r.max_depth)
        want = (s.state_count, s.unique_state_count, s.max_depth)
        if got != want or sorted(r.discoveries.items()) != sorted(
            s.discoveries.items()
        ):
            err = (
                f"fleet parity failure on job {i}: {got} / "
                f"{sorted(r.discoveries.items())} != 1-replica {want} / "
                f"{sorted(s.discoveries.items())}"
            )
            break

    def pct(sorted_ms, q):
        return sorted_ms[min(int(q * (len(sorted_ms) - 1)), len(sorted_ms) - 1)]

    states = sum(r.state_count for r in results)
    out = {
        "states": states,
        "unique": sum(r.unique_state_count for r in results),
        "sec": round(sec, 4),
        "states_per_sec": states / max(sec, 1e-9),
        "compile_sec": 0.0,  # compiles inside both wall clocks (A/B fair)
        "n_replicas": n_replicas,
        "n_jobs": len(jobs),
        "fleet_jobs_per_sec": round(len(jobs) / max(sec, 1e-9), 4),
        "sec_one_replica": round(one_sec, 4),
        "vs_one_replica": round(one_sec / max(sec, 1e-9), 3),
        "fleet_p50_ms": round(pct(lat_ms, 0.50), 1),
        "fleet_p99_ms": round(pct(lat_ms, 0.99), 1),
        "fleet_steals": stats["steals"],
        "fleet_requeued": stats["requeued_jobs"],
    }
    return out, err


def device_search_autoscale(max_replicas: int = 3):
    """BENCH_AUTOSCALE=1 row: the autoscaler A/B (ISSUE 17). The SAME
    mixed job burst runs twice — once through a fleet pinned at 1 replica,
    once through a fleet that STARTS at 1 replica with an aggressive
    Autoscaler allowed up to `max_replicas` — and the row reports both
    throughputs, the ratio, the autoscaled run's p99, and the control
    loop's own evidence (replicas_high_water, scale_outs, scale_ins).
    Parity = every autoscaled job's counts and discovery fingerprints
    equal its fixed-1 twin's: scaling mid-burst must be invisible in the
    answers, only in the wall clock."""
    use_compile_cache()
    from stateright_tpu.service import (
        AutoscaleConfig,
        Autoscaler,
        ServiceFleet,
    )
    from stateright_tpu.tensor.models import (
        TensorIncrementLock,
        TensorTwoPhaseSys,
    )

    m3, m4, mi = (
        TensorTwoPhaseSys(3), TensorTwoPhaseSys(4), TensorIncrementLock(4)
    )
    jobs = [m3] * 3 + [m4] * 3 + [mi] * 2

    def run(n_max, autoscale):
        fleet = ServiceFleet(
            n_replicas=1,
            background=True,
            max_resident=2,
            service_kwargs=dict(batch_size=1024, table_log2=17),
        )
        scaler = None
        if autoscale:
            # Aggressive bands: any queue is "over", one tick is enough,
            # short cooldown — the burst should force growth fast enough
            # to show up inside one bench row's wall clock.
            scaler = Autoscaler(fleet, AutoscaleConfig(
                min_replicas=1,
                max_replicas=n_max,
                queue_high=1.0,
                scale_out_after=1,
                scale_in_after=6,
                cooldown_ticks=2,
            ))
            scaler.start(interval_s=0.1)
        t0 = time.monotonic()
        handles = [fleet.submit(m) for m in jobs]
        fleet.drain(timeout=1800)
        sec = time.monotonic() - t0
        results = [h.result() for h in handles]
        lat_ms = sorted(
            (h._job.finished_at - h._job.submitted_at) * 1000.0
            for h in handles
        )
        counters = dict(scaler.counters) if scaler else {}
        if scaler is not None:
            scaler.close()
        fleet.close()
        return sec, results, lat_ms, counters

    fixed_sec, fixed_results, _fixed_lat, _ = run(1, autoscale=False)
    sec, results, lat_ms, counters = run(max_replicas, autoscale=True)

    err = None
    for i, (r, s) in enumerate(zip(results, fixed_results)):
        got = (r.state_count, r.unique_state_count, r.max_depth)
        want = (s.state_count, s.unique_state_count, s.max_depth)
        if got != want or sorted(r.discoveries.items()) != sorted(
            s.discoveries.items()
        ):
            err = (
                f"autoscale parity failure on job {i}: {got} / "
                f"{sorted(r.discoveries.items())} != fixed-1 {want} / "
                f"{sorted(s.discoveries.items())}"
            )
            break

    def pct(sorted_ms, q):
        return sorted_ms[min(int(q * (len(sorted_ms) - 1)), len(sorted_ms) - 1)]

    states = sum(r.state_count for r in results)
    out = {
        "states": states,
        "unique": sum(r.unique_state_count for r in results),
        "sec": round(sec, 4),
        "states_per_sec": states / max(sec, 1e-9),
        "compile_sec": 0.0,  # compiles inside both wall clocks (A/B fair)
        "n_jobs": len(jobs),
        "auto_max_replicas": max_replicas,
        "auto_jobs_per_sec": round(len(jobs) / max(sec, 1e-9), 4),
        "auto_p50_ms": round(pct(lat_ms, 0.50), 1),
        "auto_p99_ms": round(pct(lat_ms, 0.99), 1),
        "auto_replicas_high_water": counters.get("replicas_high_water", 0),
        "auto_scale_outs": counters.get("scale_outs", 0),
        "auto_scale_ins": counters.get("scale_ins", 0),
        "sec_fixed_one": round(fixed_sec, 4),
        "vs_fixed_one": round(fixed_sec / max(sec, 1e-9), 3),
    }
    return out, err


def device_search_blob(n_replicas: int = 2):
    """BENCH_BLOB=1 row: local-vs-wire checkpoint-backend overhead A/B
    (ISSUE 15, managed dialects ISSUE 20). The SAME mixed job set runs
    through an N-replica in-proc fleet once per backend —
    requeue-resume checkpoint plane + lease fence on a local directory,
    then on the in-proc blob emulator (faults/blobstore.py: HTTP
    conditional puts, bounded retry, CRC'd generations), then on the
    s3 and gcs managed-dialect emulators (faults/blobdialect.py:
    SigV4 / OAuth-bearer signing plus the credential chain per op) —
    and each measured overhead lands next to that backend client's own
    op/retry counters. Parity = every wire-side job's counts and
    discoveries equal its local twin's (the backend must be
    bit-identical, only slower by the wire + signing)."""
    use_compile_cache()
    from stateright_tpu.faults.blobstore import serve_blobd, uri_client
    from stateright_tpu.service import ServiceFleet
    from stateright_tpu.tensor.models import (
        TensorIncrementLock,
        TensorTwoPhaseSys,
    )

    m3, mi = TensorTwoPhaseSys(3), TensorIncrementLock(4)
    jobs = [m3] * 4 + [mi] * 2

    def run(fleet_kw):
        fleet = ServiceFleet(
            n_replicas=n_replicas,
            background=True,
            max_resident=2,
            service_kwargs=dict(batch_size=1024, table_log2=17),
            **fleet_kw,
        )
        t0 = time.monotonic()
        handles = [fleet.submit(m) for m in jobs]
        fleet.drain(timeout=1800)
        sec = time.monotonic() - t0
        results = [h.result() for h in handles]
        fleet.close()
        return sec, results

    def run_wire(dialect):
        """One timed leg on an in-proc wire backend: the native blob
        emulator or an s3/gcs managed dialect (whose endpoint +
        credential environment is installed for the leg's duration —
        the fleet is in-proc, so os.environ is the live config)."""
        srv = serve_blobd(dialect=dialect)
        saved = {k: os.environ.get(k) for k in srv.env}
        os.environ.update(srv.env)
        root = srv.root_uri + "/bench"
        try:
            sec, results = run(
                {"ckpt_dir": root + "/ckpt", "lease_dir": root + "/leases"}
            )
            client, _name = uri_client(root)
            counters = dict(client.counters)
        finally:
            for key, old in saved.items():
                if old is None:
                    os.environ.pop(key, None)
                else:
                    os.environ[key] = old
            srv.shutdown()
        return sec, results, counters

    run({})  # untimed warm-up: compiles land here, not in any timed leg
    sec_local, local_results = run({})
    sec_blob, blob_results, blob_counters = run_wire("blob")
    sec_s3, s3_results, s3_counters = run_wire("s3")
    sec_gcs, gcs_results, gcs_counters = run_wire("gcs")

    err = None
    for leg, results in (
        ("blob", blob_results), ("s3", s3_results), ("gcs", gcs_results)
    ):
        for i, (r, s) in enumerate(zip(results, local_results)):
            got = (r.state_count, r.unique_state_count, r.max_depth)
            want = (s.state_count, s.unique_state_count, s.max_depth)
            if got != want or sorted(r.discoveries.items()) != sorted(
                s.discoveries.items()
            ):
                err = (
                    f"{leg}-backend parity failure on job {i}: "
                    f"{got} != {want}"
                )
                break
        if err is not None:
            break

    def overhead_pct(sec):
        return round((sec - sec_local) / max(sec_local, 1e-9) * 100.0, 2)

    states = sum(r.state_count for r in blob_results)
    out = {
        "states": states,
        "unique": sum(r.unique_state_count for r in blob_results),
        "sec": round(sec_blob, 4),
        "states_per_sec": states / max(sec_blob, 1e-9),
        "compile_sec": 0.0,  # compiles paid by the untimed warm-up run
        "n_replicas": n_replicas,
        "n_jobs": len(jobs),
        "sec_local_fs": round(sec_local, 4),
        "blob_overhead_pct": overhead_pct(sec_blob),
        "blob_ops": int(blob_counters.get("ops", 0)),
        "blob_retries": int(blob_counters.get("retries", 0)),
        "sec_s3": round(sec_s3, 4),
        "s3_overhead_pct": overhead_pct(sec_s3),
        "s3_ops": int(s3_counters.get("ops", 0)),
        "s3_retries": int(s3_counters.get("retries", 0)),
        "sec_gcs": round(sec_gcs, 4),
        "gcs_overhead_pct": overhead_pct(sec_gcs),
        "gcs_ops": int(gcs_counters.get("ops", 0)),
        "gcs_retries": int(gcs_counters.get("retries", 0)),
    }
    return out, err


def device_search_semantics(model_name: str = "single_copy", n: int = 6):
    """BENCH_SEMANTICS=1 row: cold-vs-optimized A/B of the dedup-first
    verdict plane (semantics/canonical.py + batch.py) on a register-model
    anchor's PROPERTY-EVALUATION phase. The anchor is the single-copy
    register with n clients / 2 servers (the not-linearizable config, so
    most verdicts are the expensive exhaustive refutations), its first 6000
    DFS states' history testers — the post-dedup batch a checker block
    hands the plane. Side A evaluates every tester through the pre-PR
    cache-only path (canonical plane disabled, per-identity lru memo only);
    side B clears all caches and runs ONE batched plane call (canonical
    collapse + witness guidance + native-parallel search). Acceptance:
    >= 2x wall-clock with bit-identical verdict booleans."""
    use_compile_cache()
    from stateright_tpu.actor import Network
    from stateright_tpu.examples.single_copy_register import (
        SingleCopyModelCfg,
    )
    from stateright_tpu.semantics import (
        canonical,
        clear_serialization_caches,
    )
    from stateright_tpu.semantics.batch import evaluate_batch

    model = SingleCopyModelCfg(
        client_count=n,
        server_count=2,
        network=Network.new_unordered_nonduplicating(),
    ).into_model()

    # The anchor's post-dedup testers, depth-first (shared enumerator —
    # the smoke script measures the same batch shape).
    from stateright_tpu.semantics.batch import collect_history_testers

    testers, n_unique = collect_history_testers(model, 6000)

    # Side A: the pre-PR cache-only path (per-identity lru memo, fresh).
    clear_serialization_caches()
    prev = canonical.set_enabled(False)
    t0 = time.monotonic()
    legacy = [t.serialized_history() is not None for t in testers]
    sec_legacy = time.monotonic() - t0
    canonical.set_enabled(prev)

    # Side B: the dedup-first plane, cold (both caches cleared).
    clear_serialization_caches()
    counters0 = dict(canonical.CACHE.counters)
    t0 = time.monotonic()
    optimized = evaluate_batch(testers)
    sec = time.monotonic() - t0
    stats = canonical.CACHE.stats()
    delta = {
        k: stats[k] - counters0.get(k, 0)
        for k in (
            "canonical_collapsed", "witness_guided_hits", "full_searches",
            "batch_parallel_evals",
        )
    }

    err = None
    if optimized != legacy:
        err = "semantics parity failure: plane verdicts != cache-only verdicts"
    speedup = round(sec_legacy / max(sec, 1e-9), 2)
    if err is None and speedup < 2.0:
        # The acceptance bar is part of the row contract, not just prose.
        err = (
            f"dedup-first plane only {speedup}x faster than the cache-only "
            "path (acceptance >= 2x)"
        )

    out = {
        "states": len(testers),
        "unique": n_unique,
        "sec": round(sec, 4),
        "states_per_sec": len(testers) / max(sec, 1e-9),
        "compile_sec": 0.0,  # host-only phase: nothing compiles
        "sec_legacy": round(sec_legacy, 4),
        "semantics_speedup": speedup,
        "verdict_negatives": int(legacy.count(False)),
        "canonical_collapsed": int(delta["canonical_collapsed"]),
        "witness_guided_hits": int(delta["witness_guided_hits"]),
        "full_searches": int(delta["full_searches"]),
        "batch_parallel_evals": int(delta["batch_parallel_evals"]),
    }
    return out, err


def device_search_simulation(model_name: str = "2pc", n: int = 3):
    """BENCH_SIM=1 row: cold A/B of the fourth checker mode on the 2pc-3
    anchor (CPU rehearsal) — the host thread-pool `SimulationChecker` vs
    the device walk engine (tensor/simulation.py), both running random
    walks until the same generated-state budget. Walks are counted on the
    host side by a counting chooser (`new_state` fires once per trace) and
    on the device side by the engine's own telemetry; both sides exclude
    compile (the device side times rounds 2+ of one engine — continuous
    batching makes those steady-state). Acceptance: device >= 2x host
    walks/s, identical property verdicts on the anchor (abort agreement
    found, safety never violated), and same-seed device runs bit-identical
    (counts + discoveries)."""
    use_compile_cache()
    from stateright_tpu.checker.simulation import UniformChooser
    from stateright_tpu.examples.two_phase_commit import TwoPhaseSys
    from stateright_tpu.tensor.models import TensorTwoPhaseSys
    from stateright_tpu.tensor.simulation import DeviceSimulation

    target = 60_000

    class CountingChooser(UniformChooser):
        def __init__(self):
            self.walks = 0

        def new_state(self, seed):
            self.walks += 1
            return super().new_state(seed)

    chooser = CountingChooser()
    t0 = time.monotonic()
    host = (
        TwoPhaseSys(n)
        .checker()
        .target_state_count(target)
        .spawn_simulation(seed=0, chooser=chooser)
        .join()
    )
    sec_host = time.monotonic() - t0
    host_walks = chooser.walks
    host_states = host.state_count()
    host_found = set(host.discoveries())

    def fresh():
        return DeviceSimulation(
            TensorTwoPhaseSys(n), seed=0, traces=1024, max_depth=64,
            dedup="shared", table_log2=18,
        )

    def measure(sim):
        """Round 1 absorbs the compile; time rounds 2+ to the same state
        budget (continuous batching makes every round steady-state)."""
        r = sim.run()
        base_states, base_walks = r.state_count, sim._totals["walks"]
        t0 = time.monotonic()
        while r.state_count - base_states < target:
            r = sim.run()
        sec = time.monotonic() - t0
        return (
            r,
            sec,
            r.state_count - base_states,
            sim._totals["walks"] - base_walks,
        )

    sim = fresh()
    r, sec, dev_states, dev_walks = measure(sim)
    sim_b = fresh()
    r_b, _sec_b, dev_states_b, dev_walks_b = measure(sim_b)

    tel = r.detail["telemetry"]
    host_wps = host_walks / max(sec_host, 1e-9)
    dev_wps = dev_walks / max(sec, 1e-9)
    speedup = round(dev_wps / max(host_wps, 1e-9), 2)

    err = None
    if (dev_states, dev_walks, r.unique_state_count, sorted(r.discoveries)) \
            != (dev_states_b, dev_walks_b, r_b.unique_state_count,
                sorted(r_b.discoveries)):
        err = "simulation determinism failure: same-seed runs differ"
    dev_found = set(r.discoveries)
    for found, side in ((host_found, "host"), (dev_found, "device")):
        if err is None and "abort agreement" not in found:
            err = f"simulation verdict failure: {side} missed abort agreement"
        if err is None and "consistent" in found:
            err = f"simulation verdict failure: {side} violated safety"
    if err is None and speedup < 2.0:
        # The acceptance bar is part of the row contract, not just prose.
        err = (
            f"device simulation only {speedup}x host walks/s "
            "(acceptance >= 2x)"
        )

    out = {
        "states": dev_states,
        "unique": r.unique_state_count,
        "sec": round(sec, 4),
        "states_per_sec": dev_states / max(sec, 1e-9),
        "compile_sec": 0.0,  # both sides measured post-compile (A/B fair)
        "sec_host_sim": round(sec_host, 4),
        "host_states_per_sec": round(host_states / max(sec_host, 1e-9), 1),
        "sim_walks_per_sec": round(dev_wps, 1),
        "host_walks_per_sec": round(host_wps, 1),
        "sim_speedup": speedup,
        "sim_lane_util": tel["lane_util"],
        "sim_restarts": tel["restarts"],
        "sim_dedup_hit_rate": tel["dedup_hit_rate"],
        "sim_bit_identical": err is None or "determinism" not in err,
    }
    return out, err


def device_search_corpus(model_name: str = "2pc", n: int = 4):
    """BENCH_CORPUS=1 row: cold-vs-warm A/B of the cross-job warm-start
    corpus (store/corpus.py, ROADMAP item 4). Two tiered services with a
    pre-compiled step each (a throwaway first submission absorbs the
    compile on BOTH sides, so the ratio is pure search time): the cold
    side re-explores the anchor from scratch; the corpus side's first
    submission published the visited set, so its measured submission
    preloads the entry and completes warm. Acceptance: warm >= 5x faster,
    results bit-identical, and a corrupted entry (one flipped byte) is
    detected by the ckptio CRC and ignored — the third submission runs
    cold and still completes correctly.

    Corpus v2 edit-warm sub-rows: `warm_speedup_near` re-checks the same
    definition under a RETUNED lowering (table_log2 + 1) — the family
    index serves the published set through the near rung; and
    `warm_speedup_partial` cancels a run past half the space — the cut
    publishes the visited prefix + frontier snapshot and the successor
    continues from it. Both must be >= 2x over their post-compile cold
    reference with bit-identical results."""
    use_compile_cache()
    import tempfile

    from stateright_tpu.service import CheckService

    model, _batch, _tl2, _run_kwargs, _ekw, golden, _cs = _build_workload(
        model_name, n
    )
    svc_kw = dict(
        batch_size=1024,
        table_log2=18,
        store="tiered",
        high_water=0.9,
        summary_log2=18,
        background=False,
    )

    def timed_submit(svc, **opts):
        t0 = time.monotonic()
        h = svc.submit(model, **opts)
        svc.drain(timeout=1800)
        return time.monotonic() - t0, h.result()

    # Cold reference: corpus-less service, post-compile second submission.
    cold_svc = CheckService(**svc_kw)
    timed_submit(cold_svc)  # compile warm-up (timing discarded)
    cold_sec, cold_r = timed_submit(cold_svc)
    cold_svc.close()

    with tempfile.TemporaryDirectory(prefix="srtpu-corpus-") as corpus_dir:
        warm_svc = CheckService(corpus_dir=corpus_dir, **svc_kw)
        timed_submit(warm_svc)  # compile warm-up + corpus publish
        warm_sec, warm_r = timed_submit(warm_svc)
        warm_corpus = dict(warm_r.detail.get("corpus") or {})

        # Satellite: flip one payload byte in the published entry — the
        # CRC footer must catch it and the next submission must complete
        # correctly COLD (never wrong results). (The directory also holds
        # the v2 family index; target the ENTRY generation specifically.)
        import glob as _glob

        from stateright_tpu.faults.ckptio import corrupt_one_byte

        corrupt_one_byte(
            [
                p
                for p in _glob.glob(os.path.join(corpus_dir, "corpus-*.npz"))
                if "-family-" not in os.path.basename(p)
                and "-spec-" not in os.path.basename(p)
            ][0]
        )
        _sec3, third_r = timed_submit(warm_svc)
        stats = warm_svc.stats()
        corrupt_detected = stats.get("corpus", {}).get(
            "corrupt_entries", 0
        ) >= 1
        warm_svc.close()

        # -- corpus v2 edit-warm A/B: the NEAR rung ------------------------
        # Same definition, retuned lowering (table_log2 + 1): the retuned
        # key misses the exact rung, and the family index serves the
        # published set for a delta-proportional (here: replay) re-check.
        # Submissions with a huge target_state_count carry a different
        # finish signature, so they absorb compile and give the retuned
        # cold reference WITHOUT ever publishing a near-replayable member.
        near_kw = dict(svc_kw, table_log2=svc_kw["table_log2"] + 1)
        near_svc = CheckService(corpus_dir=corpus_dir, **near_kw)
        big = 1 << 40
        timed_submit(near_svc, target_state_count=big)  # compile warm-up
        cold_near_sec, _ = timed_submit(near_svc, target_state_count=big + 1)
        warm_near_sec, near_r = timed_submit(near_svc)
        near_corpus = dict(near_r.detail.get("corpus") or {})
        near_svc.close()

    # -- corpus v2 edit-warm A/B: the PARTIAL rung -------------------------
    # A mid-run cancel publishes the visited prefix + frontier snapshot;
    # the successor continues from the cut instead of starting over. The
    # cut lands past two thirds of the space so the continuation's win is
    # the prefix it skips (cold reference: the post-compile cold_sec
    # above) with headroom over the preload/pump overhead.
    with tempfile.TemporaryDirectory(prefix="srtpu-corpus-p-") as pdir:
        part_svc = CheckService(corpus_dir=pdir, **svc_kw)
        hp = part_svc.submit(model)
        cut = 2 * (golden[0] if golden else 1 << 20) // 3
        while part_svc.pump() and hp._job.state_count < cut:
            pass
        hp.cancel()
        warm_part_sec, part_r = timed_submit(part_svc)
        part_corpus = dict(part_r.detail.get("corpus") or {})
        part_svc.close()

    err = None
    for name, r in (
        ("warm", warm_r),
        ("corrupt-cold", third_r),
        ("near-warm", near_r),
        ("partial-warm", part_r),
    ):
        got = (r.state_count, r.unique_state_count, r.max_depth)
        want = (
            cold_r.state_count, cold_r.unique_state_count, cold_r.max_depth,
        )
        if got != want or sorted(r.discoveries.items()) != sorted(
            cold_r.discoveries.items()
        ):
            err = (
                f"corpus parity failure ({name}): {got} / "
                f"{sorted(r.discoveries.items())} != cold {want} / "
                f"{sorted(cold_r.discoveries.items())}"
            )
            break
    if err is None and golden is not None and (
        warm_r.state_count, warm_r.unique_state_count,
    ) != golden:
        err = (
            f"corpus golden failure: "
            f"{(warm_r.state_count, warm_r.unique_state_count)} != {golden}"
        )
    if err is None and not warm_corpus.get("warm_start"):
        err = "corpus warm submission did not take the warm path"
    if err is None and not corrupt_detected:
        err = "corrupted corpus entry was not detected by the CRC check"
    warm_speedup = round(cold_sec / max(warm_sec, 1e-9), 2)
    if err is None and warm_speedup < 5.0:
        # The acceptance bar is part of the row contract, not just prose.
        err = (
            f"warm submission only {warm_speedup}x faster than cold "
            "(acceptance >= 5x)"
        )
    warm_speedup_near = round(cold_near_sec / max(warm_near_sec, 1e-9), 2)
    warm_speedup_partial = round(cold_sec / max(warm_part_sec, 1e-9), 2)
    if err is None and near_corpus.get("warm_kind") != "near":
        err = (
            "retuned submission did not take the near rung "
            f"(detail: {near_corpus})"
        )
    if err is None and part_corpus.get("warm_kind") != "partial":
        err = (
            "post-cut submission did not take the partial rung "
            f"(detail: {part_corpus})"
        )
    if err is None and warm_speedup_near < 2.0:
        err = (
            f"near-warm submission only {warm_speedup_near}x faster than "
            "cold (acceptance >= 2x)"
        )
    if err is None and warm_speedup_partial < 2.0:
        err = (
            f"partial-warm submission only {warm_speedup_partial}x faster "
            "than cold (acceptance >= 2x)"
        )

    out = {
        "states": warm_r.state_count,
        "unique": warm_r.unique_state_count,
        "sec": round(warm_sec, 4),
        "states_per_sec": warm_r.state_count / max(warm_sec, 1e-9),
        "compile_sec": 0.0,  # both sides measured post-compile (A/B fair)
        "sec_cold": round(cold_sec, 4),
        "warm_speedup": warm_speedup,
        "warm_speedup_near": warm_speedup_near,
        "warm_speedup_partial": warm_speedup_partial,
        "corpus_preloaded": int(warm_corpus.get("preloaded_states", 0)),
        "corrupt_detected": corrupt_detected,
    }
    return out, err


def device_search_delta(model_name: str = "2pc", n: int = 4):
    """BENCH_DELTA=1 row: Spec-CI definition-delta A/B on the anchor —
    cold exploration of a property-EDITED model vs the same edited model
    served from the corpus on the "delta" rung (store/specdelta.py). The
    corpus side first publishes the base model's visited set, then
    submits an edited model whose first property condition is negated
    (class name preserved, so the geometry digest keeps it in the same
    spec family); the delta classifier names the edit "properties-only"
    and replays the published set with only the changed verdict
    re-evaluated. Acceptance: rung == "delta", class == properties-only,
    counts bit-identical to the edited model's own cold run, the edited
    property's discovery present, and >= 2x over the post-compile cold
    reference."""
    use_compile_cache()
    import dataclasses
    import tempfile

    from stateright_tpu.service import CheckService

    model, _batch, _tl2, _run_kwargs, _ekw, _golden, _cs = _build_workload(
        model_name, n
    )
    svc_kw = dict(
        batch_size=1024,
        table_log2=18,
        store="tiered",
        high_water=0.9,
        summary_log2=18,
        background=False,
    )

    # The one-line edit: negate the first property's condition. The
    # subclass keeps the base class's NAME — the geometry digest includes
    # it, and a renamed model is a different spec family, not an edit.
    base_cls = type(model)

    def _edited_props(self, _base=base_cls):
        props = list(_base.properties(self))
        p0 = props[0]
        props[0] = dataclasses.replace(
            p0,
            name=p0.name + " negated",
            condition=lambda m, s, _c=p0.condition: ~_c(m, s),
        )
        return props

    edited_cls = type(
        base_cls.__name__, (base_cls,), {"properties": _edited_props}
    )
    edited = edited_cls(
        **{f.name: getattr(model, f.name)
           for f in dataclasses.fields(model)}
    )

    def timed_submit(svc, m):
        t0 = time.monotonic()
        h = svc.submit(m)
        svc.drain(timeout=1800)
        return time.monotonic() - t0, h.result()

    # Cold reference: corpus-less service, post-compile second submission
    # of the EDITED model (the delta rung's counts must match this).
    cold_svc = CheckService(**svc_kw)
    timed_submit(cold_svc, edited)  # compile warm-up (timing discarded)
    cold_sec, cold_r = timed_submit(cold_svc, edited)
    cold_svc.close()

    with tempfile.TemporaryDirectory(prefix="srtpu-delta-") as corpus_dir:
        svc = CheckService(corpus_dir=corpus_dir, **svc_kw)
        timed_submit(svc, model)  # base model: compile + corpus publish
        # A delta replay never publishes, so a second edited submission
        # takes the delta rung again — the first absorbs the edited
        # model's own kernel compiles (the cold side absorbed its in the
        # warm-up above; the measured ratio is pure replay-vs-search).
        timed_submit(svc, edited)
        delta_sec, delta_r = timed_submit(svc, edited)
        delta_corpus = dict(delta_r.detail.get("corpus") or {})
        stats = dict(svc.stats().get("corpus") or {})
        svc.close()

    err = None
    got = (delta_r.state_count, delta_r.unique_state_count, delta_r.max_depth)
    want = (cold_r.state_count, cold_r.unique_state_count, cold_r.max_depth)
    if got != want or sorted(delta_r.discoveries) != sorted(
        cold_r.discoveries
    ):
        err = (
            f"delta parity failure: {got} / {sorted(delta_r.discoveries)} "
            f"!= cold {want} / {sorted(cold_r.discoveries)}"
        )
    if err is None and delta_corpus.get("warm_kind") != "delta":
        err = (
            "edited submission did not take the delta rung "
            f"(detail: {delta_corpus})"
        )
    if err is None and delta_corpus.get("delta_class") != "properties-only":
        err = (
            "edit was not classified properties-only "
            f"(detail: {delta_corpus})"
        )
    if err is None and not stats.get("delta_hits"):
        err = f"delta_hits counter did not advance (stats: {stats})"
    warm_speedup_delta = round(cold_sec / max(delta_sec, 1e-9), 2)
    if err is None and warm_speedup_delta < 2.0:
        err = (
            f"delta submission only {warm_speedup_delta}x faster than "
            "cold (acceptance >= 2x)"
        )

    out = {
        "states": delta_r.state_count,
        "unique": delta_r.unique_state_count,
        "sec": round(delta_sec, 4),
        "states_per_sec": delta_r.state_count / max(delta_sec, 1e-9),
        "compile_sec": 0.0,  # both sides measured post-compile (A/B fair)
        "sec_cold": round(cold_sec, 4),
        "warm_speedup_delta": warm_speedup_delta,
        "delta_class": delta_corpus.get("delta_class"),
    }
    return out, err


def device_search_sharded(model_name: str, n: int, n_chips: int = 8):
    """Run the multi-chip sharded engine over a mesh of `n_chips` (virtual
    CPU devices when real multi-chip hardware is absent — the bench marks
    the result accordingly)."""
    use_compile_cache()
    import jax

    from stateright_tpu.parallel import ShardedSearch, make_mesh

    # engine_kwargs are mostly resident-engine options (donate_chunks) with
    # no sharded equivalent — intentionally dropped — except the tiered
    # store, which the sharded engine supports as per-shard rank-local
    # spill.
    model, batch, table_log2, run_kwargs, engine_kwargs, golden, closure_s = (
        _build_workload(model_name, n)
    )
    store_kwargs = {
        k: engine_kwargs[k]
        for k in ("store", "high_water", "low_water", "summary_log2")
        if k in engine_kwargs
    }
    n_chips = min(n_chips, len(jax.devices()))
    search = ShardedSearch(
        model,
        mesh=make_mesh(n_chips),
        batch_size=batch // 2,
        table_log2=max(table_log2 - 2, 10),
        **store_kwargs,
    )
    best, out = _time_search(search, run_kwargs, repeats=2, closure_s=closure_s)
    out.update(
        n_chips=n_chips,
        virtual_mesh=jax.devices()[0].platform == "cpu",
        per_chip_unique=best.detail["per_chip_unique"],
    )
    _attach_store_stats(out, search)
    _attach_telemetry(out, best)
    return out, _parity_err(model_name, n, best, golden)


# -- static-analysis budget row (BENCH_ANALYSIS=1) -----------------------------

#: detail.analysis row shape (pinned by tests/test_bench_contract.py).
ANALYSIS_ROW_FIELDS = ("srlint_findings", "knob_drift", "engines", "clean")
#: per-engine audit fields inside detail.analysis.engines.<name>.
ANALYSIS_ENGINE_FIELDS = (
    "step_hbm_bytes", "step_flops", "transfer_bytes", "model_bytes",
    "ratio", "ratio_ok", "violations", "skipped",
)


def worker_analysis() -> dict:
    """`bench.py --worker-analysis`: the static-analysis budget row —
    srlint over the repo, knob-registry drift, and the three engine
    anchors' audited step totals (abstract jaxpr tracing on CPU; nothing
    executes on a device). Runs in a fresh subprocess so the forced
    8-device CPU mesh never leaks into the TPU workers."""
    from stateright_tpu.analysis.anchors import audit_anchors
    from stateright_tpu.analysis.srlint import lint_paths
    from stateright_tpu.knobs import check_registry

    findings = lint_paths()
    drift = check_registry()
    engines = {}
    violations = 0
    ratios_ok = True
    for name, ar in audit_anchors().items():
        if ar.skipped:
            engines[name] = {"skipped": ar.skipped}
            continue
        s = ar.report.summary()
        engines[name] = {
            "step_hbm_bytes": s["step_hbm_bytes"],
            "step_flops": s["step_flops"],
            "transfer_bytes": s["transfer_bytes"],
            "model_bytes": round(ar.model_bytes),
            "ratio": round(ar.ratio, 2),
            "ratio_ok": ar.ratio_ok,
            "violations": s["violations"],
        }
        violations += len(s["violations"])
        ratios_ok = ratios_ok and ar.ratio_ok
    # Same verdict the CLI gate reaches over the project's own passes
    # (srlint, drift, jaxpr violations, costmodel cross-check). ruff/mypy
    # are deliberately excluded: the artifact row must not flip with what
    # happens to be installed on the bench image.
    return {
        "srlint_findings": len(findings),
        "knob_drift": len(drift),
        "engines": engines,
        "clean": not findings and not drift and violations == 0 and ratios_ok,
    }


def analysis_row(timeout: float = 600.0) -> dict:
    """Run worker_analysis in a subprocess (fresh jax, CPU backend, 8 host
    devices for the sharded anchor) and return its row; errors become an
    {"error": ...} row, never a bench death."""
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--worker-analysis"],
            env=env,
            capture_output=True,
            text=True,
            timeout=timeout,
        )
        line = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else ""
        payload = json.loads(line)
    except Exception as e:  # noqa: BLE001 — reporting must never kill a run
        log(f"analysis row failed: {e}")
        return {"error": str(e)}
    if payload.get("error"):
        log(f"analysis row failed: {payload['error']}")
        return {"error": payload["error"]}
    return payload["result"]


# -- main ----------------------------------------------------------------------

# Per-workload fields copied into detail.device verbatim when present. The
# last three are the tiered store's per-tier occupancy counters (hot-tier
# fill fraction, states spilled to the host tier, spill-event count) —
# degradation past HBM is observable in every artifact
# (tests/test_bench_contract.py pins the keys).
DEVICE_DETAIL_FIELDS = (
    "virtual_mesh", "n_chips", "per_chip_unique",
    "closure_sec", "bytes_per_state", "cpu_bytes_per_state", "hbm_frac",
    "hot_fill", "spilled_states", "spill_events",
    # Check-service row (BENCH_SERVICE=1): mixed-job-batch throughput and
    # the serial A/B ratio (>1 = continuous batching beats serial runs).
    "n_jobs", "jobs_per_sec", "vs_serial", "serial_sec",
    "service_steps", "serial_steps",
    # Telemetry spine (stateright_tpu/obs/): the step-telemetry digest of
    # the run, and — on the BENCH_OBS=1 A/B row — the telemetry-off wall
    # time plus the measured on-vs-off overhead (acceptance: <= 2%).
    "telemetry", "sec_off", "telemetry_overhead_pct",
    # Flight recorder (obs/events.py, BENCH_OBS=1 journal sub-row): the
    # journal-off wall time, the measured journal-on overhead through the
    # check service (acceptance: <= 5%), and how many events the run
    # recorded.
    "sec_journal_off", "journal_overhead_pct", "journal_events",
    # Calibration observatory (obs/calib.py, BENCH_CALIB=1 A/B row): the
    # measured-vs-predicted join's digest (predicted/measured ms, drift
    # ratio, per-term attribution) plus the comparator-off wall time and
    # the measured on-vs-off overhead (acceptance: within noise — the
    # comparator is host arithmetic at chunk granularity).
    "calib", "calib_overhead_pct",
    # Chaos plane / supervisor (BENCH_FAULTS=1 A/B row): the recovery
    # digest plus the unsupervised wall time and the measured supervisor
    # overhead with injection disabled (expected within noise).
    "faults", "sec_unsupervised", "supervisor_overhead_pct",
    # Pallas insert A/B (BENCH_PALLAS=1 row): the capped-insert wall time
    # next to the pallas run's, and the speed ratio (>1 = pallas wins).
    "sec_capped", "pallas_vs_capped",
    # Service fleet (BENCH_FLEET=1 row): N-replica mixed-set throughput vs
    # the same jobs through one replica (>1 = scale-out wins), plus the
    # fleet run's submit→result latency digest and robustness counters.
    "n_replicas", "fleet_jobs_per_sec", "sec_one_replica",
    "vs_one_replica", "fleet_p50_ms", "fleet_p99_ms",
    "fleet_steals", "fleet_requeued",
    # Autoscaling fleet (BENCH_AUTOSCALE=1 row): fixed 1-replica vs a
    # fleet that starts at 1 and grows under the Autoscaler on the same
    # burst — both throughputs, the ratio, the autoscaled run's latency
    # digest, and the control loop's own scale-event evidence.
    "auto_max_replicas", "auto_jobs_per_sec", "auto_p50_ms", "auto_p99_ms",
    "auto_replicas_high_water", "auto_scale_outs", "auto_scale_ins",
    "sec_fixed_one", "vs_fixed_one",
    # Blob checkpoint backend (BENCH_BLOB=1 row): the local-filesystem
    # wall time next to the blob-emulator run's (`sec`), the measured
    # overhead percentage, and the blob client's op/retry counters —
    # the "object store costs only the wire, never the answers" claim.
    # Managed-dialect legs (s3 = SigV4-signed dialect emulator, gcs =
    # OAuth-bearer dialect emulator) carry the same trio each: signed
    # wall time, overhead vs sec_local_fs, and that client's counters.
    "sec_local_fs", "blob_overhead_pct", "blob_ops", "blob_retries",
    "sec_s3", "s3_overhead_pct", "s3_ops", "s3_retries",
    "sec_gcs", "gcs_overhead_pct", "gcs_ops", "gcs_retries",
    # Warm-start corpus (BENCH_CORPUS=1 row): the cold wall time next to
    # the warm submission's (`sec`), the cold/warm ratio (acceptance >=
    # 5x), the preloaded-state count, and the corrupted-entry CRC verdict
    # (True = a flipped byte was detected and the run fell back cold).
    # v2 edit-warm sub-rows: the near rung (same definition, retuned
    # lowering — family-index replay) and the partial rung (mid-run cut,
    # frontier continuation), each against a post-compile cold reference
    # (acceptance >= 2x each).
    "sec_cold", "warm_speedup", "warm_speedup_near", "warm_speedup_partial",
    "corpus_preloaded", "corrupt_detected",
    # Spec-CI definition delta (BENCH_DELTA=1 row): the property-edit
    # cold reference next to the delta-rung submission's (`sec`), the
    # measured ratio (acceptance >= 2x with bit-identical counts and the
    # re-evaluated verdict present), and the classifier's named edit
    # class ("properties-only" on this row).
    "warm_speedup_delta", "delta_class",
    # Dedup-first semantics (BENCH_SEMANTICS=1 row): the cache-only wall
    # time next to the plane's (`sec`), the measured ratio (acceptance >=
    # 2x with bit-identical verdicts), and the plane's own evidence —
    # classes collapsed by canonicalization, witness-guided resolutions,
    # full searches actually run, and native-pool evaluations.
    "sec_legacy", "semantics_speedup", "verdict_negatives",
    "canonical_collapsed", "witness_guided_hits", "full_searches",
    "batch_parallel_evals",
    # Device random simulation (BENCH_SIM=1 row): the host walker's wall
    # time and rates next to the device engine's (`sec`/`states_per_sec`),
    # the walks/s ratio (acceptance >= 2x), the lane-utilization and
    # restart evidence of continuous walk batching, the shared-table dedup
    # hit rate, and the same-seed determinism verdict.
    "sec_host_sim", "host_states_per_sec", "sim_walks_per_sec",
    "host_walks_per_sec", "sim_speedup", "sim_lane_util", "sim_restarts",
    "sim_dedup_hit_rate", "sim_bit_identical",
)


def device_detail(v: dict) -> dict:
    """One workload's detail.device row (shape pinned by the bench-contract
    tests): headline rate + the optional DEVICE_DETAIL_FIELDS."""
    return {
        "states_per_sec": round(v["states_per_sec"], 1),
        "sec": v["sec"],
        **{f: v[f] for f in DEVICE_DETAIL_FIELDS if f in v},
    }


def headline_summary(dev: dict, base: dict, smoke: bool = False):
    """Headline metric for the one-line JSON: Paxos-3 (the BASELINE.json
    north-star workload).

    Contract: ``value``/``vs_baseline`` describe the DEVICE engine only.
    When no device result exists both are None — never the C++ baseline
    number — so a dashboard reading ``value`` cannot mistake the baseline
    for a result.  Returns ``(metric, value, vs_baseline)``.
    """
    headline_dev = dev.get("paxos-3")
    headline_base = base.get("paxos-3")
    if headline_dev is not None:
        value = headline_dev["states_per_sec"]
        metric = (
            "paxos-3 generated states/sec (device whole-search, on-device "
            "linearizability; 1,194,428 unique states)"
        )
    else:
        value = None
        if smoke:
            why = "paxos-3 not run in smoke mode"
        elif dev:
            why = "device failed on paxos-3"
        else:
            why = "device unavailable"
        metric = (
            f"paxos-3 generated states/sec (no device result: {why}; "
            "CPU baseline in detail.cpu_baseline)"
        )
    vs_baseline = (
        round(value / headline_base["states_per_sec"], 3)
        if headline_base and value
        else None
    )
    return metric, round(value, 1) if value is not None else None, vs_baseline


def main(argv: list | None = None) -> int:
    detail: dict = {}
    errors: list[str] = []

    # --baseline-threads N: additionally run every C++ baseline workload
    # with an explicit N-thread row (the north-star
    # denominator is the MULTITHREADED reference checker; the default row
    # keeps baseline_bfs's own hardware_concurrency default). Malformed
    # values are ignored rather than killing the bench.
    args = list(sys.argv[1:] if argv is None else argv)
    baseline_threads = None
    if "--baseline-threads" in args:
        i = args.index("--baseline-threads")
        try:
            baseline_threads = max(1, int(args[i + 1]))
        except (IndexError, ValueError):
            log("ignoring malformed --baseline-threads")
    if baseline_threads is None and (os.cpu_count() or 1) > 1:
        # Multicore host: record the pinned threads=N multithreaded row by
        # DEFAULT (the flag used to be opt-in, so older artifacts carry
        # only threads:1 denominators). --baseline-threads still overrides.
        baseline_threads = os.cpu_count()
        log(f"multicore host: recording threads={baseline_threads} "
            "baseline rows by default")
    for a in args:
        # A typo'd flag silently dropped on a chip run would cost the
        # multithread rows the flag exists for — say so loudly.
        if a.startswith("--") and a != "--baseline-threads":
            log(f"unknown bench.py flag {a!r} ignored "
                "(known: --baseline-threads N)")

    # BENCH_SMOKE=1: harness smoke mode — smallest baseline + device
    # workloads only, so the full pipeline (C++ baseline, device probe,
    # worker subprocess, parity oracle, JSON emission) can be exercised in
    # minutes. The emitted line is marked so it can't be mistaken for a
    # real benchmark.
    smoke = os.environ.get("BENCH_SMOKE") == "1"
    baseline_cfgs = (
        (("paxos", 2, 1), ("2pc", 4, 1))
        if smoke
        else (
            ("paxos", 2, 3),
            ("paxos", 3, 3),
            ("2pc", 4, 3),
            ("increment_lock", 6, 3),
            # The full reference bench.sh config; one repeat — it runs for
            # minutes and best-of-N would eat the device budget.
            ("2pc", 10, 1),
        )
    )

    exe = compile_baseline()
    base = {}
    if exe:
        for model, n, repeats in baseline_cfgs:
            runs = [(f"{model}-{n}", None)]
            if baseline_threads is not None:
                # Always emit the pinned row when asked — -t1 is meaningful
                # on a multicore host, where the default row runs at
                # hardware_concurrency.
                runs.append(
                    (f"{model}-{n}-t{baseline_threads}", baseline_threads)
                )
            for key, threads in runs:
                r = run_baseline(exe, model, n, repeats=repeats, threads=threads)
                if not r:
                    continue
                gen_gold, uniq_gold = GOLDEN[(model, n)]
                if (r["states"], r["unique"]) != (gen_gold, uniq_gold):
                    errors.append(
                        f"baseline {key} golden mismatch: "
                        f"(gen={r['states']}, unique={r['unique']}) != "
                        f"(gen={gen_gold}, unique={uniq_gold})"
                    )
                if r["violations"]:
                    errors.append(
                        f"baseline {key} reported {r['violations']} "
                        "property violations (expected none)"
                    )
                base[key] = r
                log(
                    f"baseline {key}: {r['states']} states in "
                    f"{r['sec']}s ({r['states_per_sec']:.0f}/s, "
                    f"{r['threads']} threads)"
                )
    detail["cpu_baseline"] = {
        k: {
            "states_per_sec": round(v["states_per_sec"], 1),
            "sec": v["sec"],
            "threads": v["threads"],
        }
        for k, v in base.items()
    }

    device_error = None
    dev: dict = {}
    dev_errors: dict = {}
    ok, probe_err = probe_device()
    if not ok:
        device_error = f"device probe failed: {probe_err}"
    else:
        # Smallest-to-largest: each validated workload de-risks the next.
        # Workloads are independent — one failing (e.g. OOM at a big table
        # size) must not misreport the device as unavailable for the others.
        # (name, n, timeout, mode, extra env) — the sharded multi-chip config
        # runs on a virtual 8-device CPU mesh (real multi-chip hardware is
        # not reachable from this harness; the result is marked
        # virtual_mesh=true).
        virtual8 = {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        }
        # BENCH_TIMEOUT_SCALE: multiply per-workload subprocess timeouts —
        # CPU-backend rehearsals need it (the completed 2pc-10 CPU run takes
        # ~115 min vs the 50-min TPU budget).
        # Malformed/zero/negative values fall back to 1 (never crash the
        # bench or zero the timeouts mid-run).
        try:
            tscale = float(os.environ.get("BENCH_TIMEOUT_SCALE", "1"))
        except ValueError:
            tscale = 1.0
        if not (0 < tscale < float("inf")):  # rejects NaN/inf/<=0 too
            tscale = 1.0
        workloads = (
            (("2pc", 4, 600.0, "--worker", None),)
            if smoke
            else (
                ("2pc", 4, 1500.0, "--worker", None),
                ("inclock", 6, 1500.0, "--worker", None),
                ("inclock-sym", 6, 1500.0, "--worker", None),
                ("paxos", 2, 1500.0, "--worker", None),
                ("abd-ordered", 16, 1500.0, "--worker", None),
                ("paxos", 3, 1500.0, "--worker", None),
                ("paxos5s4c", 10, 2400.0, "--worker", None),
                ("paxos5s4c", 10, 2400.0, "--worker-sharded", virtual8),
                ("2pc", 10, 3000.0, "--worker", None),
            )
        )
        # BENCH_SERVICE=1: add the check-service mixed-job row (8 jobs
        # through one shared table vs the same jobs serially; the ratio
        # lands in detail.device["service-mixed-8"].vs_serial).
        if os.environ.get("BENCH_SERVICE") == "1" and not smoke:
            workloads += (("service-mixed", 8, 2400.0, "--worker-service", None),)
        # BENCH_OBS=1: add the telemetry on/off A/B on the r4 anchor row
        # (paxos-3 — the costmodel's pinned 12.9 ms/step workload); the
        # measured overhead lands in
        # detail.device["paxos-3-obs"].telemetry_overhead_pct.
        if os.environ.get("BENCH_OBS") == "1" and not smoke:
            workloads += (("paxos", 3, 2400.0, "--worker-obs", None),)
            # ...and the flight-recorder journal on/off A/B on the 2pc-4
            # anchor THROUGH the check service (where the journal actually
            # emits: one event per fused step + job transitions; the
            # measured overhead lands in
            # detail.device["2pc-4-journal"].journal_overhead_pct,
            # acceptance <= 5%).
            workloads += (("2pc", 4, 2400.0, "--worker-journal", None),)
        # BENCH_CALIB=1: add the calibration-comparator on/off A/B on the
        # 2pc-4 anchor (resident engine; the measured-vs-predicted join of
        # obs/calib.py costs host arithmetic per 32-step chunk — the
        # measured overhead lands in
        # detail.device["2pc-4-calib"].calib_overhead_pct, acceptance
        # within noise, with the drift digest in .calib).
        if os.environ.get("BENCH_CALIB") == "1" and not smoke:
            workloads += (("2pc", 4, 2400.0, "--worker-calib", None),)
        # BENCH_FAULTS=1: add the supervisor-overhead A/B on the 2pc-4
        # anchor (plain resident vs run_supervised with injection off; the
        # measured overhead lands in
        # detail.device["2pc-4-faults"].supervisor_overhead_pct).
        if os.environ.get("BENCH_FAULTS") == "1" and not smoke:
            workloads += (("2pc", 4, 2400.0, "--worker-faults", None),)
        # BENCH_PALLAS=1: add the pallas-vs-capped insert A/B on the 2pc-4
        # and paxos-2 anchors (resident engine; the Pallas route-then-probe
        # kernel vs the r6 capped insert — the measured ratio lands in
        # detail.device["<wl>-pallas"].pallas_vs_capped next to the
        # costmodel's committed ranking, costmodel.predict_ranking).
        if os.environ.get("BENCH_PALLAS") == "1" and not smoke:
            workloads += (
                ("2pc", 4, 2400.0, "--worker-pallas", None),
                ("paxos", 2, 2400.0, "--worker-pallas", None),
            )
        # BENCH_FLEET=1: add the N-replica fleet scale-out A/B on the mixed
        # job set (the same composition as the service row, through a
        # 3-replica fleet vs 1 replica; jobs/s ratio + p50/p99 latency land
        # in detail.device["fleet-mixed-3"]).
        if os.environ.get("BENCH_FLEET") == "1" and not smoke:
            workloads += (("fleet-mixed", 3, 2400.0, "--worker-fleet", None),)
        # BENCH_AUTOSCALE=1: add the autoscaler A/B on the mixed job set
        # (fixed 1-replica fleet vs a fleet that starts at 1 and may grow
        # to 3 under an aggressive Autoscaler; jobs/s both ways, the
        # ratio, p99, and replicas_high_water/scale_outs/scale_ins land
        # in detail.device["fleet-auto-3"]).
        if os.environ.get("BENCH_AUTOSCALE") == "1" and not smoke:
            workloads += (("fleet-auto", 3, 2400.0, "--worker-autoscale", None),)
        # BENCH_BLOB=1: add the local-vs-blob checkpoint-backend overhead
        # A/B (the mixed job set through a 2-replica fleet with the
        # requeue-resume plane + lease fence on a local dir vs the blob
        # emulator; overhead + blob op/retry counters land in
        # detail.device["fleet-blob-2"]).
        if os.environ.get("BENCH_BLOB") == "1" and not smoke:
            workloads += (("fleet-blob", 2, 2400.0, "--worker-blob", None),)
        # BENCH_CORPUS=1: add the cross-job warm-start cold-vs-warm A/B on
        # the 2pc-4 anchor (second submission of the same content key
        # through a corpus-enabled tiered service; the measured ratio
        # lands in detail.device["2pc-4-corpus"].warm_speedup — acceptance
        # >= 5x with bit-identical results — next to the corrupted-entry
        # CRC verdict).
        if os.environ.get("BENCH_CORPUS") == "1" and not smoke:
            workloads += (("2pc", 4, 2400.0, "--worker-corpus", None),)
        # BENCH_DELTA=1: add the Spec-CI definition-delta A/B on the
        # 2pc-4 anchor (publish the base model, then submit a
        # property-edited variant; the classifier names the edit and the
        # delta rung replays the published set with only the changed
        # verdict re-evaluated — the measured ratio lands in
        # detail.device["2pc-4-delta"].warm_speedup_delta, acceptance
        # >= 2x with bit-identical counts).
        if os.environ.get("BENCH_DELTA") == "1" and not smoke:
            workloads += (("2pc", 4, 2400.0, "--worker-delta", None),)
        # BENCH_SEMANTICS=1: add the dedup-first verdict-plane A/B on the
        # single-copy-register 6c2s anchor (property-evaluation phase only,
        # host-side; the measured ratio lands in
        # detail.device["single_copy-6-semantics"].semantics_speedup —
        # acceptance >= 2x with bit-identical verdicts).
        if os.environ.get("BENCH_SEMANTICS") == "1" and not smoke:
            workloads += (
                ("single_copy", 6, 2400.0, "--worker-semantics", None),
            )
        # BENCH_SIM=1: add the fourth checker mode's host-vs-device A/B on
        # the 2pc-3 anchor (host thread-pool SimulationChecker vs the
        # continuous-batched device walk engine to the same state budget;
        # the measured walks/s ratio lands in
        # detail.device["2pc-3-sim"].sim_speedup — acceptance >= 2x with
        # identical verdicts and bit-identical same-seed device runs).
        if os.environ.get("BENCH_SIM") == "1" and not smoke:
            workloads += (("2pc", 3, 2400.0, "--worker-sim", None),)
        for model, n, wl_timeout, mode, env_extra in workloads:
            key = f"{model}-{n}" + (
                {
                    "--worker-sharded": "-sharded8",
                    "--worker-obs": "-obs",
                    "--worker-journal": "-journal",
                    "--worker-calib": "-calib",
                    "--worker-faults": "-faults",
                    "--worker-pallas": "-pallas",
                    "--worker-corpus": "-corpus",
                    "--worker-delta": "-delta",
                    "--worker-semantics": "-semantics",
                    "--worker-sim": "-sim",
                    "--worker-fleet": "",
                    "--worker-autoscale": "",
                    "--worker-blob": "",
                }.get(mode, "")
            )
            r, perr = device_search_subprocess(
                model,
                n,
                timeout=wl_timeout * tscale,
                mode=mode,
                env_extra=env_extra,
            )
            if r is None:
                # No result is a failure even without an error string (e.g.
                # a truncated worker payload missing both keys).
                dev_errors[key] = perr or "worker returned no result"
                log(f"device {key} failed: {perr or 'no result'}")
                continue
            if perr:
                errors.append(perr)
            dev[key] = r
            log(
                f"device {key}: {r['states']} states in {r['sec']}s "
                f"({r['states_per_sec']:.0f}/s, compile {r['compile_sec']}s)"
            )
        if dev_errors and not dev:
            device_error = "; ".join(
                f"{k}: {v}" for k, v in dev_errors.items()
            )
    detail["device"] = {k: device_detail(v) for k, v in dev.items()}
    # Sharding overhead ratio: sharded-N vs the
    # single-device engine on the SAME workload — <1 means the sharded
    # engine's per-step machinery (send-buffer scatters, all-to-all,
    # N-fold insert width) costs more than it parallelizes on this mesh.
    for k, v in dev.items():
        if k.endswith("-sharded8") and k[: -len("-sharded8")] in dev:
            single = dev[k[: -len("-sharded8")]]["states_per_sec"]
            if single > 0:
                detail["device"][k]["vs_single_device"] = round(
                    v["states_per_sec"] / single, 3
                )
    if dev_errors:
        detail["device_errors"] = dev_errors

    # BENCH_ANALYSIS=1: the static-analysis budget row — srlint finding
    # count, knob drift, and each engine anchor's audited step
    # FLOP/byte/transfer totals vs the costmodel (abstract CPU tracing in a
    # fresh subprocess; no device). Keys pinned in test_bench_contract.py:
    # the budget trend is part of the artifact, so a BENCH_r*.json can
    # answer "did the compiled step program grow" without re-profiling.
    if os.environ.get("BENCH_ANALYSIS") == "1" and not smoke:
        detail["analysis"] = analysis_row()

    metric, value, vs_baseline = headline_summary(dev, base, smoke=smoke)
    if smoke:
        metric = f"[SMOKE MODE — not a benchmark] {metric}"

    out = {
        "metric": metric,
        "value": value,
        "unit": "states/sec",
        "vs_baseline": vs_baseline,
        "detail": detail,
    }
    if device_error:
        out["device_error"] = device_error
    if errors:
        out["error"] = "; ".join(errors)
    print(json.dumps(out), flush=True)
    return 1 if errors else 0


def worker_main(model_name: str, n: int, mode: str = "--worker") -> int:
    """`bench.py --worker[-sharded|-service] MODEL N`: run one device
    workload, print one JSON line {"result": ..., "error": ...} on stdout."""
    try:
        if mode == "--worker-service":
            r, perr = device_search_service(n)
        elif mode == "--worker-fleet":
            r, perr = device_search_fleet(n)
        elif mode == "--worker-autoscale":
            r, perr = device_search_autoscale(n)
        elif mode == "--worker-blob":
            r, perr = device_search_blob(n)
        elif mode == "--worker-sharded":
            r, perr = device_search_sharded(model_name, n)
        elif mode == "--worker-obs":
            r, perr = device_search_obs(model_name, n)
        elif mode == "--worker-journal":
            r, perr = device_search_journal(model_name, n)
        elif mode == "--worker-calib":
            r, perr = device_search_calib(model_name, n)
        elif mode == "--worker-faults":
            r, perr = device_search_faults(model_name, n)
        elif mode == "--worker-pallas":
            r, perr = device_search_pallas(model_name, n)
        elif mode == "--worker-corpus":
            r, perr = device_search_corpus(model_name, n)
        elif mode == "--worker-delta":
            r, perr = device_search_delta(model_name, n)
        elif mode == "--worker-semantics":
            r, perr = device_search_semantics(model_name, n)
        elif mode == "--worker-sim":
            r, perr = device_search_simulation(model_name, n)
        else:
            r, perr = device_search(model_name, n)
        print(json.dumps({"result": r, "error": perr}), flush=True)
        return 0
    except Exception:  # noqa: BLE001
        traceback.print_exc()
        err = traceback.format_exc(limit=3).strip().splitlines()[-1]
        print(json.dumps({"result": None, "error": err}), flush=True)
        return 1


if __name__ == "__main__":
    if len(sys.argv) == 4 and sys.argv[1] in (
        "--worker", "--worker-sharded", "--worker-service", "--worker-obs",
        "--worker-journal", "--worker-calib", "--worker-faults",
        "--worker-pallas",
        "--worker-fleet", "--worker-autoscale", "--worker-blob",
        "--worker-corpus", "--worker-delta", "--worker-semantics",
        "--worker-sim",
    ):
        sys.exit(worker_main(sys.argv[2], int(sys.argv[3]), mode=sys.argv[1]))
    if len(sys.argv) == 2 and sys.argv[1] == "--worker-analysis":
        try:
            print(
                json.dumps({"result": worker_analysis(), "error": None}),
                flush=True,
            )
            sys.exit(0)
        except Exception:  # noqa: BLE001 — one-JSON-line contract
            traceback.print_exc()
            err = traceback.format_exc(limit=3).strip().splitlines()[-1]
            print(json.dumps({"result": None, "error": err}), flush=True)
            sys.exit(1)
    try:
        sys.exit(main())
    except Exception:  # noqa: BLE001 — the one-JSON-line contract is absolute
        traceback.print_exc()
        print(
            json.dumps(
                {
                    "metric": "paxos-3 states/sec",
                    "value": 0.0,
                    "unit": "states/sec",
                    "vs_baseline": None,
                    "error": traceback.format_exc(limit=2)
                    .strip()
                    .splitlines()[-1],
                }
            ),
            flush=True,
        )
        sys.exit(1)
