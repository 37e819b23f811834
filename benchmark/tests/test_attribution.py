"""The attribution reader (harness/attribution.py): each device op's
`tf_op` path from the trace's event metadata, self time per named scope,
and device programs inside host spans — on two small profiles recorded on
a v5e chip. Also pins what the accepted readers give on the first profile,
so that nothing added beside them moves them."""

import glob
import os
import shutil

import pytest

from harness import attribution, roofline, spec, trace
from harness.runner import Reading, _reduce, reduce_trace
from harness.window import Check

DATA = os.path.join(os.path.dirname(__file__), "data")
SMALL = os.path.join(DATA, "v5e_small.xplane.pb")
SCOPED = os.path.join(DATA, "scoped", "v5e_scoped.xplane.pb")
SCOPES = ("expand", "fingerprint", "insert")
#: record_scoped_profile.REPLAY_PROGRAMS, per check.
REPLAY_PROGRAMS = 3


def test_scope_of_takes_the_innermost_scope_in_the_path():
    path = "jit(search)/while/body/insert/jit(sort)/sort:"
    assert attribution.scope_of(path, SCOPES) == "insert"
    assert attribution.scope_of("jit(f)/expand/fingerprint/xor:",
                                SCOPES) == "fingerprint"
    assert attribution.scope_of("jit(f)/while/body/add:", SCOPES) == \
        attribution.UNSCOPED
    assert attribution.scope_of("", SCOPES) == attribution.UNSCOPED


def test_op_paths_read_with_the_times_trace_py_reads():
    ops = attribution.read_ops(SMALL)
    t = trace.read_xplane(SMALL, span_names=None)
    assert [[o[:2] for o in chip] for chip in ops] == \
        [[o[:2] for o in chip] for chip in t.ops]
    paths = {tf_op for chip in ops for *_, tf_op in chip}
    assert "jit(step)/while/body/closed_call/jit(sort)/sort:" in paths
    assert "jit(other)/reduce_sum:" in paths


def test_accepted_readers_keep_their_values_on_the_first_profile(tmp_path):
    window_s, busy, program_busy, per_check, breakdown = reduce_trace(
        SMALL, [Check(0.0, 1.0), Check(1.0, 2.0)], "jit_step")
    assert (window_s, busy, program_busy) == pytest.approx(
        (0.112244303, 0.046264222, 0.046248528), rel=1e-9)
    assert per_check == pytest.approx([0.023131794, 0.023132428], rel=1e-9)
    assert breakdown["device_ops"][0] == ["sort.13 (u32[1048576],",
                                          pytest.approx(0.045986572)]
    assert breakdown["idle_gaps"][0] == ["bench.spawn",
                                         pytest.approx(0.032594521)]
    shutil.copy(SMALL, tmp_path)
    records = [Check(float(i), i + 1.0, generated=1000, unique=500, steps=20)
               for i in range(2)]
    r = Reading(**_reduce(str(tmp_path), records, "jit_step"),
                setup={"compile_s": 1.5}, lanes=21,
                peak_bytes_per_s=roofline.peak_bytes_per_s("TPU v5 lite"))
    want = {
        "cache_load_s": 1.5,
        "host_s_per_check.exhaustive": 0.976867889,
        "host_s_per_check.first-discovery": 0.976867889,
        "idle_pct.exhaustive": 58.782565561478876,
        "idle_pct.first-discovery": 58.782565561478876,
        "search_roofline_pct": 0.0005068966398935647,
        "step_ms": 1.1562132,
    }
    got = {}
    for path in glob.glob(os.path.join(spec.BENCH_DIR, "metrics", "*.py")):
        name = os.path.basename(path)[:-3]
        if name in want:
            got[name] = spec.load_reader(path)(r)
    assert got == pytest.approx(want, rel=1e-9)


@pytest.fixture(scope="module")
def scoped():
    if not os.path.exists(SCOPED):
        pytest.skip("no recorded scoped profile under tests/data/scoped")
    t = trace.read_xplane(SCOPED, span_names=None)
    return t, attribution.read_ops(SCOPED)


def test_ops_are_attributed_to_their_scopes(scoped):
    _, ops = scoped
    labels = {}
    for _, _, tf_op in ops[0]:
        if tf_op.startswith("jit(step)/"):
            labels.setdefault(attribution.scope_of(tf_op, SCOPES),
                              set()).add(tf_op.rpartition("/")[2])
    # The sort is the insert; the multiply-add of `expand` fused into the
    # xor of `fingerprint`, and XLA names a fusion by its root op, so no op
    # is left in `expand`; the remainder lies outside every scope.
    assert labels == {"insert": {"sort:"}, "fingerprint": {"xor:"},
                      attribution.UNSCOPED: {"rem:"}}


def test_scoped_and_unscoped_self_time_is_the_program_busy_time(scoped):
    t, ops = scoped
    runs = [m for m in t.modules[0] if m[2].startswith("jit_step")]
    assert len(runs) == 2
    by_scope = attribution.scope_self_ns(ops[0], SCOPES, runs)
    busy = trace.covered(trace.union(trace.within(t.ops[0], runs)),
                         min(m[0] for m in runs), max(m[1] for m in runs))
    assert sum(by_scope.values()) == pytest.approx(busy, rel=0.02)
    assert by_scope["insert"] > 0


def test_programs_inside_replay_spans_and_span_seconds(scoped):
    t, _ = scoped
    replays = [s for s in t.spans if s[2] == "path.replay"]
    assert len(replays) == 2
    assert attribution.executions_within(t.modules[0], replays) == \
        2 * REPLAY_PROGRAMS
    assert attribution.span_seconds(t.spans, "path.replay") == \
        pytest.approx(sum((e - s) / 1e9 for s, e, _ in replays))
