"""The engines' shared result assembly (tensor/search_host.py) on synthetic
per-shard arrays: the one-chip summary layout is the N = 1 case, witnesses
may sit on any shard, a run is complete only when every shard drained, and
a warm-start replay reports the publisher's counts."""

import numpy as np
import pytest

from stateright_tpu import Property
from stateright_tpu.obs import NULL_TRACER
from stateright_tpu.tensor.search_host import SearchHost, Stages, summary_fields

PROPS = [
    Property.always("safe", lambda m, s: True),
    Property.sometimes("reached", lambda m, s: True),
    Property.eventually("done", lambda m, s: True),
]
P = len(PROPS)


class _Host(SearchHost):
    """The result half of an engine: no store, no telemetry."""

    def __init__(self, warm=None):
        self.props = PROPS
        self._warm = warm
        self._warm_kind = "exact" if warm else None
        self._warm_states = 7 if warm else 0
        self._ring = None
        self._calib = None
        self._stages = Stages("test", NULL_TRACER)

    def store_stats(self):
        return None


def _summary(n, gen=0, unique=(), depth=(), discovered=0, head=(), tail=(),
             steps=(), disc=None):
    """Chunk summaries `[n, 12 + 2P]` in the engines' column order."""
    s = np.zeros((n, 12 + 2 * P), np.uint32)
    s[:, 0], s[:, 1] = gen & 0xFFFFFFFF, gen >> 32
    s[:, 2], s[:, 3], s[:, 4] = unique, depth, discovered
    s[:, 5], s[:, 6], s[:, 8] = head, tail, steps
    for (shard, prop), fp in (disc or {}).items():
        s[shard, 12 + prop] = fp & 0xFFFFFFFF
        s[shard, 12 + P + prop] = fp >> 32
    return s


def test_one_shard_reads_the_resident_summary_layout():
    gen = (5 << 32) | 9  # a count past 2^32 spans both words
    s = _summary(1, gen=gen, unique=[1568], depth=[14], discovered=0b011,
                 head=[1568], tail=[1568], steps=[40],
                 disc={(0, 0): 0xAB_0000_0001, (0, 1): 0x12_0000_0003})
    r = _Host()._result(0.0, True, **summary_fields(s, P, timed_out=False))
    assert (r.state_count, r.unique_state_count, r.max_depth, r.steps) == (
        gen, 1568, 14, 40
    )
    assert r.discoveries == {"safe": 0xAB_0000_0001,
                             "reached": 0x12_0000_0003}
    assert r.complete and r.detail is None


def test_four_shards_sum_max_and_take_each_first_nonzero_witness():
    s = _summary(
        4, gen=8258, unique=[400, 390, 378, 400], depth=[14, 13, 14, 12],
        discovered=0b011, head=[400, 390, 378, 400],
        tail=[400, 390, 378, 400], steps=[41, 41, 41, 41],
        # "safe" seen on shards 2 and 3: shard order picks 2; "reached"
        # only on shard 1; "done" has a stale witness but no bit.
        disc={(2, 0): 0x2_0000_0022, (3, 0): 0x3_0000_0033,
              (1, 1): 0x1_0000_0011, (0, 2): 0x9_0000_0099},
    )
    fields = summary_fields(s, P, timed_out=False)
    r = _Host()._result(0.0, True, extra={"per_chip_unique": [1]}, **fields)
    assert (r.state_count, r.unique_state_count, r.max_depth) == (
        8258, 1568, 14
    )
    assert r.discoveries == {"safe": 0x2_0000_0022, "reached": 0x1_0000_0011}
    assert r.complete
    assert list(r.detail) == ["per_chip_unique"]


@pytest.mark.parametrize("timed_out", [False, True])
def test_incomplete_when_a_shard_has_not_drained_or_time_ran_out(timed_out):
    head = [10, 10, 10, 10] if timed_out else [10, 10, 7, 10]
    s = _summary(4, gen=100, unique=[10] * 4, depth=[3] * 4, head=head,
                 tail=[10] * 4, steps=[5] * 4)
    r = _Host()._result(0.0, True, **summary_fields(s, P, timed_out))
    assert not r.complete
    assert r.unique_state_count == 40


@pytest.mark.parametrize("drained", [True, False])
def test_warm_replay_reports_the_publishers_counts(drained):
    warm = {"state_count": 8258, "unique_count": 1568, "max_depth": 14,
            "discoveries": {"reached": 77}}
    s = _summary(2, gen=12, unique=[3, 2], depth=[2, 1], discovered=0,
                 head=[3, 2], tail=[3, 2 if drained else 4], steps=[2, 2])
    r = _Host(warm)._result(0.0, False, **summary_fields(s, P, False))
    got = (r.state_count, r.unique_state_count, r.max_depth, r.discoveries)
    if drained:
        assert got == (8258, 1568, 14, {"reached": 77})
    else:  # a continuation still running keeps its own counts
        assert got == (12, 5, 2, {})
    assert r.detail["corpus"] == {
        "warm_start": True, "preloaded_states": 7, "warm_kind": "exact",
    }
    assert set(r.detail["phases"]) == {
        "trace_s", "lower_s", "compile_s", "execute_s",
    }
