"""Telemetry spine (stateright_tpu/obs/): ring-drain correctness against
golden counts, Chrome trace-event validation, Prometheus scrape parsing on
both HTTP servers, reporter rate/fill fields, and the detail schema.

Speed note: the engine-backed tests share module-scoped results (one compile
per engine) and use the small 2pc-3 space — the tier-1 suite is near its
timeout budget.
"""

import io
import json
import re
import urllib.request

import numpy as np
import pytest

from stateright_tpu.obs import (
    N_COLS,
    STEP_COLS,
    StepRing,
    Tracer,
    flatten_metrics,
    render_prometheus,
    validate_detail,
)
from stateright_tpu.obs.schema import (
    DETAIL_KEYS,
    PHASES_DETAIL_KEYS,
    SERVICE_DETAIL_KEYS,
    TELEMETRY_KEYS,
)

GOLD_2PC3 = (1_146, 288)  # generated, unique (ref examples/2pc.rs:153-159)


# -- pure ring mechanics -------------------------------------------------------


def _device_ring(rows_by_step: dict, capacity: int) -> np.ndarray:
    """Simulate the device ring: row for step i lives at i % capacity."""
    ring = np.zeros((capacity, N_COLS), dtype=np.uint32)
    for i, row in rows_by_step.items():
        ring[i % capacity] = row
    return ring


def _row(step, generated=10, claimed=5):
    r = np.zeros(N_COLS, dtype=np.uint32)
    r[STEP_COLS.index("step")] = step
    r[STEP_COLS.index("generated")] = generated
    r[STEP_COLS.index("claimed")] = claimed
    r[STEP_COLS.index("active")] = 3
    return r


def test_ring_drain_exact_and_wrap():
    cap = 8
    ring = StepRing(cap)
    # First drain: 5 steps, all resident.
    dev = _device_ring({i: _row(i) for i in range(5)}, cap)
    assert ring.drain(dev, 5) == 5
    assert ring.steps == 5 and ring.dropped_steps == 0
    # Second drain: steps 5..20 — only the last `cap` survive on device.
    dev = _device_ring({i: _row(i) for i in range(20)}, cap)
    captured = ring.drain(dev, 20)
    assert captured == cap
    assert ring.steps == 20
    # dropped = steps without a RETAINED row (never drained + evicted from
    # the host retention window): 20 total - 8 retained.
    assert ring.dropped_steps == 20 - cap
    assert len(ring._rows) == cap
    # Totals still count every row that was drained (5 + 8), even the ones
    # retention later evicted.
    assert ring.generated_total == (5 + cap) * 10
    # Idempotent at the same watermark.
    assert ring.drain(dev, 20) == 0
    # A restarted engine (step counter went backwards) resets the ring.
    ring.drain(_device_ring({0: _row(0)}, cap), 1)
    assert ring.steps == 1 and ring.dropped_steps == 0


def test_ring_drain_sharded_aggregates_and_imbalance():
    cap = 8
    ring = StepRing(cap)
    rings = np.zeros((2, cap, N_COLS), dtype=np.uint32)
    for shard, claimed in ((0, 6), (1, 2)):
        for i in range(4):
            rings[shard, i] = _row(i, generated=10, claimed=claimed)
    assert ring.drain_sharded(rings, 4) == 4
    assert ring.generated_total == 2 * 4 * 10  # extensive: summed
    assert ring.claimed_total == 4 * (6 + 2)
    s = ring.summary(table_size=1 << 10, batch_size=64)
    assert s["shard_imbalance"] == pytest.approx(6 / 4, abs=1e-3)
    assert s["steps"] == 4 and s["dropped_steps"] == 0


def test_ring_counts_append_tiles_per_step_and_shard():
    # ceil(claimed / tile) per drained row: 0, 4, 5, 9 claims in tiles of 4
    # are 0 + 1 + 2 + 3 tiles; a scatter-append ring (tile 0) counts none.
    claims = (0, 4, 5, 9)
    dev = _device_ring({i: _row(i, claimed=c) for i, c in enumerate(claims)}, 8)
    ring, plain = StepRing(8, tile=4), StepRing(8)
    ring.drain(dev, 4)
    plain.drain(dev, 4)
    assert (ring.append_tiles_total, plain.append_tiles_total) == (6, 0)
    # Each chip writes its own tiles: 5 + 5 claims are 2 + 2 tiles a step,
    # not ceil(10 / 4).
    rings = np.zeros((2, 8, N_COLS), dtype=np.uint32)
    rings[:, :3] = [_row(i, claimed=5) for i in range(3)]
    sharded = StepRing(8, tile=4)
    sharded.drain_sharded(rings, 3)
    assert sharded.append_tiles_total == 3 * (2 + 2)
    # A fresh ring, or one whose engine restarted, keeps its tile.
    assert ring.fresh().tile == 4
    ring.drain(_device_ring({0: _row(0, claimed=1)}, 8), 1)
    assert (ring.tile, ring.append_tiles_total) == (4, 1)


def test_ring_summary_keys_match_schema():
    ring = StepRing(8)
    ring.append(active=4, generated=10, claimed=5, queue_len=7,
                table_claims=9, suspects=1, depth=2, step_us=123.0)
    s = ring.summary(table_size=1 << 10, batch_size=8)
    assert set(s) <= set(TELEMETRY_KEYS), set(s) - set(TELEMETRY_KEYS)
    assert s["lane_util"] == pytest.approx(0.5)
    assert s["fill"]["last"] == pytest.approx(9 / 1024, abs=1e-4)


# -- engine-backed drains vs goldens ------------------------------------------


@pytest.fixture(scope="module")
def tpc3():
    from stateright_tpu.tensor.models import TensorTwoPhaseSys

    return TensorTwoPhaseSys(3)


@pytest.fixture(scope="module")
def seed_counts(tpc3):
    from stateright_tpu.tensor.frontier import seed_init

    init, _, _, n_raw = seed_init(tpc3)
    return len(init), n_raw


def _assert_telemetry_matches(result, n0, n_raw):
    t = result.detail["telemetry"]
    assert t["dropped_steps"] == 0
    assert t["steps"] == result.steps
    # The exact conservation laws the ring must honor: every generated
    # state and every fresh claim appears in exactly one step row.
    assert t["generated_total"] == result.state_count - n_raw
    assert t["claimed_total"] == result.unique_state_count - n0
    assert validate_detail(result.detail) == []


def test_frontier_ring_totals_match_golden(tpc3, seed_counts):
    from stateright_tpu.tensor.frontier import FrontierSearch

    n0, n_raw = seed_counts
    fs = FrontierSearch(tpc3, batch_size=256, table_log2=12)
    r = fs.run()
    assert (r.state_count, r.unique_state_count) == GOLD_2PC3
    _assert_telemetry_matches(r, n0, n_raw)
    # Per-step wall times exist on the host-orchestrated engine.
    assert r.detail["telemetry"]["step_us"]["max"] > 0


def test_resident_ring_totals_match_golden(tpc3, seed_counts):
    from stateright_tpu.tensor.resident import ResidentSearch

    n0, n_raw = seed_counts
    rs = ResidentSearch(tpc3, batch_size=256, table_log2=12)
    # Chunked run: the ring drains at every chunk boundary.
    r = rs.run(budget=4)
    assert (r.state_count, r.unique_state_count) == GOLD_2PC3
    _assert_telemetry_matches(r, n0, n_raw)


def test_frontier_early_exit_counts_final_step(tpc3):
    from stateright_tpu.core.discovery import HasDiscoveries
    from stateright_tpu.tensor.frontier import FrontierSearch

    fs = FrontierSearch(tpc3, batch_size=256, table_log2=12)
    r = fs.run(finish_when=HasDiscoveries.ANY)
    assert r.discoveries  # really early-exited on the first discovery
    t = r.detail["telemetry"]
    # The exiting step's contribution is discarded by the search itself;
    # telemetry counts it as an uncaptured step so steps still reconcile.
    assert t["steps"] == r.steps
    assert t["dropped_steps"] == 1


def test_resident_telemetry_off_restores_plain_detail(tpc3):
    from stateright_tpu.tensor.resident import ResidentSearch

    rs = ResidentSearch(
        tpc3, batch_size=256, table_log2=12, telemetry=False
    )
    r = rs.run()
    assert (r.state_count, r.unique_state_count) == GOLD_2PC3
    # device store + telemetry off = only the staged call's phases
    assert set(r.detail) == {"phases"}


def test_resident_whole_search_phases_and_step_window(tpc3):
    from stateright_tpu.tensor.resident import ResidentSearch

    rs = ResidentSearch(tpc3, batch_size=256, table_log2=12)
    r = rs.run()
    assert (r.state_count, r.unique_state_count) == GOLD_2PC3
    assert validate_detail(r.detail) == []
    phases = r.detail["phases"]
    assert set(phases) == set(PHASES_DETAIL_KEYS)
    assert all(v > 0 for v in phases.values()), phases
    # The ring's window is the execute stage alone: no trace, lowering or
    # compile in the per-step wall time (the digest keeps 2 decimals).
    t = r.detail["telemetry"]
    assert t["step_us"]["mean"] == round(
        phases["execute_s"] * 1e6 / t["steps"], 2
    )
    m = rs.metrics()
    for k, v in phases.items():
        assert m[k[:-2] + "_seconds_total"] == v
    # A second run reuses the executable: no stage but execute runs.
    r2 = rs.run()
    assert (r2.state_count, r2.unique_state_count) == GOLD_2PC3
    assert r2.discoveries == r.discoveries
    p2 = r2.detail["phases"]
    assert p2["trace_s"] == p2["lower_s"] == p2["compile_s"] == 0.0
    assert p2["execute_s"] > 0
    assert rs.metrics()["compile_seconds_total"] == phases["compile_s"]


# -- tracing -------------------------------------------------------------------


def _validate_chrome_trace(doc: dict) -> list:
    """Machine validation of the Chrome trace-event format: the object form
    with a traceEvents list whose events carry name/ph/ts/pid/tid, complete
    events a non-negative dur."""
    assert isinstance(doc, dict) and isinstance(doc["traceEvents"], list)
    events = [e for e in doc["traceEvents"] if e.get("ph") != "M"]
    for e in events:
        assert {"name", "ph", "pid", "tid"} <= set(e), e
        if e["ph"] != "M":
            assert e["ts"] >= 0
        if e["ph"] == "X":
            assert e["dur"] >= 0
    return events


def test_tracer_emits_valid_chrome_trace(tmp_path):
    tracer = Tracer()
    with tracer.span("outer", cat="test", k=1):
        with tracer.span("inner", cat="test"):
            pass
    tracer.instant("marker", cat="test")
    path = tracer.save(str(tmp_path / "trace.json"))
    events = _validate_chrome_trace(json.load(open(path)))
    names = [e["name"] for e in events]
    assert names == ["inner", "outer", "marker"]  # spans close inner-first
    outer = events[1]
    assert outer["args"] == {"k": 1}


def test_spawn_tpu_trace_out_writes_perfetto_file(tpc3, tmp_path):
    out = str(tmp_path / "run.trace.json")
    checker = (
        tpc3.checker()
        .trace_out(out)
        .spawn_tpu(batch_size=256, table_log2=12)
        .join()
    )
    assert checker.unique_state_count() == GOLD_2PC3[1]
    events = _validate_chrome_trace(json.load(open(out)))
    names = {e["name"] for e in events}
    assert {"search.run", "resident.search"} <= names
    # The checker also surfaces the telemetry digest + table fill live.
    assert checker.telemetry_summary()["steps"] > 0
    assert 0 < checker.table_fill() <= 1


STAGES = ("resident.trace", "resident.lower", "resident.compile",
          "resident.execute")


@pytest.fixture(scope="module")
def traced_check(tmp_path_factory):
    """One traced spawn_tpu + discoveries() under a CPU profiler session:
    (checker, paths, the saved Chrome trace's events, host-plane event
    names of the profiler's trace). Its own model instance, whose path
    replay no other test has compiled."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from stateright_tpu.tensor.models import TensorTwoPhaseSys

    d = tmp_path_factory.mktemp("traced")
    out = str(d / "check.trace.json")
    jax.profiler.start_trace(str(d / "prof"))
    try:
        checker = (
            TensorTwoPhaseSys(3).checker()
            .trace_out(out)
            .spawn_tpu(batch_size=256, table_log2=12)
            .join()
        )
        paths = checker.discoveries()
    finally:
        jax.profiler.stop_trace()
    (xplane,) = glob.glob(str(d / "prof" / "**" / "*.xplane.pb"),
                          recursive=True)
    host = [
        e.name
        for plane in ProfileData.from_file(xplane).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
    ]
    events = _validate_chrome_trace(json.load(open(out)))
    return checker, paths, events, host


def _inside(inner, outer) -> bool:
    return (outer["ts"] <= inner["ts"]
            and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"])


def test_traced_check_spans_nest_and_carry_counters(traced_check):
    checker, paths, events, _ = traced_check
    by = {}
    for e in events:
        by.setdefault(e["name"], []).append(e)
    (search,) = by["resident.search"]
    stages = [by[name][0] for name in STAGES]
    assert all(len(by[name]) == 1 and _inside(by[name][0], search)
               for name in STAGES)
    # The four stages are the search call: they cover its span.
    assert sum(e["dur"] for e in stages) >= 0.98 * search["dur"]
    (disc,) = by["checker.discoveries"]
    walks = by["path.parents"]
    assert all(_inside(e, disc) for e in walks)
    assert sorted(e["args"]["steps"] for e in walks) == sorted(
        len(p) for p in paths.values())
    assert all(e["args"]["dispatches"] == 1 for e in walks)
    assert "path.parent_map" not in by
    replays = by["path.replay"]
    assert sorted(e["args"]["steps"] for e in replays) == sorted(
        len(p) - 1 for p in paths.values())
    assert all(_inside(e, disc) for e in replays)
    # The replay's transition program is traced once a check, then reused.
    assert all(e["args"]["traced"] in (0, 1) for e in replays)
    assert sum(e["args"]["traced"] for e in replays) == 1
    # One check id joins every span of the spawn.
    ids = {e["args"]["check"] for e in events}
    assert len(ids) == 1 and ids.pop()


def test_traced_check_answers_as_untraced(traced_check, tpc3):
    checker, paths, _, _ = traced_check
    plain = tpc3.checker().spawn_tpu(batch_size=256, table_log2=12).join()
    assert (plain.state_count(), plain.unique_state_count(),
            plain.max_depth()) == (checker.state_count(),
                                   checker.unique_state_count(),
                                   checker.max_depth())
    plain_paths = plain.discoveries()
    assert set(plain_paths) == set(paths)
    for name, p in paths.items():
        assert plain_paths[name].into_pairs() == p.into_pairs()


def test_profiler_host_plane_has_the_span_names(traced_check):
    # Annotations carry the plain span names (args stay in the JSON), so a
    # device trace's reduction can match host spans by exact name.
    _, _, events, host = traced_check
    counts = {}
    for e in events:
        counts[e["name"]] = counts.get(e["name"], 0) + 1
    assert {*STAGES, "resident.search", "search.run", "checker.discoveries",
            "path.parents", "path.replay"} <= set(counts)
    for name, n in counts.items():
        assert host.count(name) == n, name


SHARDED_STAGES = ("sharded.trace", "sharded.lower", "sharded.compile",
                  "sharded.execute")


@pytest.fixture(scope="module")
def sharded_traced_check(tmp_path_factory):
    """One traced spawn_tpu(chips=4) + discoveries() on 2pc-4: (checker,
    paths, the saved Chrome trace's events)."""
    from stateright_tpu.tensor.models import TensorTwoPhaseSys

    out = str(tmp_path_factory.mktemp("sharded") / "check.trace.json")
    checker = (
        TensorTwoPhaseSys(4).checker()
        .trace_out(out)
        .spawn_tpu(batch_size=64, table_log2=12, chips=4)
        .join()
    )
    paths = checker.discoveries()
    return checker, paths, _validate_chrome_trace(json.load(open(out)))


def test_sharded_check_spans_nest_and_stages_cover_the_search(
    sharded_traced_check,
):
    checker, paths, events = sharded_traced_check
    by = {}
    for e in events:
        by.setdefault(e["name"], []).append(e)
    (run,) = by["search.run"]
    (search,) = by["sharded.search"]
    assert _inside(search, run)
    stages = [by[name][0] for name in SHARDED_STAGES]
    assert all(len(by[name]) == 1 and _inside(by[name][0], search)
               for name in SHARDED_STAGES)
    # The four stages are the search call: their seconds add up to it.
    assert sum(e["dur"] for e in stages) == pytest.approx(search["dur"],
                                                          rel=0.01)
    phases = checker._result.detail["phases"]
    assert set(phases) == set(PHASES_DETAIL_KEYS)
    assert validate_detail(checker._result.detail) == []
    (disc,) = by["checker.discoveries"]
    walks = by["path.parents"]
    assert all(_inside(e, disc) for e in walks)
    assert sorted(e["args"]["steps"] for e in walks) == sorted(
        len(p) for p in paths.values())
    assert checker.unique_state_count() == 1568
    assert "path.parent_map" not in by
    replays = by["path.replay"]
    assert sorted(e["args"]["steps"] for e in replays) == sorted(
        len(p) - 1 for p in paths.values())
    assert all(_inside(e, disc) for e in replays)
    ids = {e["args"]["check"] for e in events}
    assert len(ids) == 1 and ids.pop()


def test_sharded_counters_are_exact_on_2pc4(sharded_traced_check):
    from stateright_tpu.tensor.frontier import seed_init

    checker, paths, _ = sharded_traced_check
    search = checker._search
    m = search.metrics()
    r = checker._result
    _, _, _, n_raw = seed_init(search.model)
    # Every generated successor is routed once; every step each of the N
    # chips sends N x C slots.
    assert m["exchange_rows_total"] == r.state_count - n_raw == 8258 - n_raw
    assert m["exchange_rows_total"] == m["generated_states"]
    assert m["exchange_slots_total"] == (
        r.steps * search.n_chips ** 2 * search.dest_capacity)
    assert m["parent_map_entries"] == 0
    assert m["parent_walk_steps_total"] == sum(len(p) for p in paths.values())
    assert m["parent_walk_dispatches_total"] == len(paths)
    assert m["replay_steps_total"] == sum(len(p) - 1 for p in paths.values())
    assert m["replay_traces_total"] == 1
    for k, v in r.detail["phases"].items():
        assert m[k[:-2] + "_seconds_total"] == v


def test_sharded_chunked_run_counts_the_same_exchange():
    from stateright_tpu.parallel.sharded import ShardedSearch, make_mesh
    from stateright_tpu.tensor.models import TensorTwoPhaseSys

    whole = ShardedSearch(TensorTwoPhaseSys(4), make_mesh(4), 64, 12)
    r = whole.run()
    chunked = ShardedSearch(TensorTwoPhaseSys(4), make_mesh(4), 64, 12)
    rc = chunked.run(budget=3)
    assert (rc.state_count, rc.steps) == (r.state_count, r.steps)
    for k in ("exchange_rows_total", "exchange_slots_total"):
        assert chunked.metrics()[k] == whole.metrics()[k] > 0
    # The chunked path runs no staged whole-search call.
    assert "phases" not in rc.detail
    assert chunked.metrics()["execute_seconds_total"] == 0.0


# -- Prometheus export ---------------------------------------------------------

_PROM_LINE = re.compile(
    r"^(# (TYPE|HELP) [a-zA-Z_:][a-zA-Z0-9_:]* ?.*"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [-+0-9.eE]+(inf|nan)?)$"
)


def _assert_prometheus_text(body: str) -> int:
    lines = [ln for ln in body.splitlines() if ln.strip()]
    for line in lines:
        assert _PROM_LINE.match(line), f"bad exposition line: {line!r}"
    samples = [ln for ln in lines if not ln.startswith("#")]
    assert samples, "no samples in scrape"
    return len(samples)


def test_render_prometheus_flattens_nested_and_lists():
    text = render_prometheus(
        {
            "src": {
                "steps": 3,
                "fill": {"last": 0.5},
                "per_chip": [1, 2],
                "flag": True,
                "skipped": None,
                "label": "tiered",  # non-numeric: dropped
            }
        }
    )
    _assert_prometheus_text(text)
    assert "stateright_src_steps 3" in text
    assert "stateright_src_fill_last 0.5" in text
    assert 'stateright_src_per_chip{index="1"} 2' in text
    assert "stateright_src_flag 1" in text
    assert "skipped" not in text and "label" not in text
    assert flatten_metrics({"a": {"b": 2}}) == {"a_b": 2}


def test_explorer_metrics_endpoint_scrapes(tpc3):
    # Host model through the on-demand Explorer — no device compile.
    from stateright_tpu.examples.two_phase_commit import TwoPhaseSys

    server = TwoPhaseSys(3).checker().serve("localhost:0")
    try:
        body = (
            urllib.request.urlopen(
                f"http://{server.address}/metrics", timeout=10
            )
            .read()
            .decode()
        )
        _assert_prometheus_text(body)
        assert "stateright_checker_unique_state_count" in body
        status = json.loads(
            urllib.request.urlopen(
                f"http://{server.address}/.status", timeout=10
            ).read()
        )
        assert "telemetry" in status  # None for host checkers, key present
    finally:
        server.shutdown()


def test_service_metrics_endpoint_and_status(tpc3):
    from stateright_tpu.service import CheckService
    from stateright_tpu.service.server import metrics_view, serve_service

    svc = CheckService(batch_size=256, table_log2=14, background=False)
    try:
        h = svc.submit(tpc3)
        svc.drain()
        assert h.result().unique_state_count == GOLD_2PC3[1]
        # The scheduler's telemetry rode every fused step.
        st = svc.stats()
        assert st["telemetry"]["steps"] == st["device_steps"] > 0
        _assert_prometheus_text(metrics_view(svc))
        server = serve_service(svc, "localhost:0")
        try:
            body = (
                urllib.request.urlopen(
                    f"http://{server.address}/metrics", timeout=10
                )
                .read()
                .decode()
            )
            _assert_prometheus_text(body)
            assert "device_steps" in body
            status = json.loads(
                urllib.request.urlopen(
                    f"http://{server.address}/.status", timeout=10
                ).read()
            )
            assert status["telemetry"]["steps"] == st["device_steps"]
        finally:
            server.shutdown()
    finally:
        svc.close()


# -- reporter fields -----------------------------------------------------------


def test_reporter_checking_line_gains_rate_and_fill():
    from stateright_tpu import WriteReporter
    from stateright_tpu.core.report import ReportData

    stream = io.StringIO()
    rep = WriteReporter(stream)
    rep.report_checking(
        ReportData(10, 5, 2, 0.5, done=False, rate=1234.6, fill=0.421)
    )
    rep.report_checking(ReportData(10, 5, 2, 0.5, done=False))
    rep.report_checking(
        ReportData(10, 5, 2, 0.5, done=True, rate=99.0, fill=0.9)
    )
    lines = stream.getvalue().splitlines()
    assert lines[0] == "Checking. states=10, unique=5, depth=2, rate=1235, fill=42.1%"
    # Without telemetry the line stays byte-identical to the reference.
    assert lines[1] == "Checking. states=10, unique=5, depth=2"
    # The Done line NEVER changes (bench harnesses grep its sec= field).
    assert lines[2] == "Done. states=10, unique=5, depth=2, sec=0.5"


# -- schema --------------------------------------------------------------------


def test_detail_schema_pins_known_vocabulary():
    # Tier counters, service keys, and telemetry keys all live in the ONE
    # documented schema.
    for k in ("hot_fill", "spilled_states", "spill_events", "per_chip_unique",
              "service", "telemetry"):
        assert k in DETAIL_KEYS
    for k in ("queue_wait", "device_steps", "lanes_held", "preemptions"):
        assert k in SERVICE_DETAIL_KEYS
    assert validate_detail(None) == []
    assert validate_detail({"telemetry": {"bogus": 1}}) == ["telemetry.bogus"]
    assert validate_detail({"mystery": 1}) == ["mystery"]
