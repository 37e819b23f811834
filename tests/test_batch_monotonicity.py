"""Batch-scaling shape tests for the capped insert (tier-1, CPU backend).

The tentpole claim: with the capped insert, the
engine's per-step cost grows AT MOST LINEARLY with batch size — the
full-batch B·log(B) sort term that made b=32768 1.6x slower than b=4096 on
paxos-3 (round 4, reproduced on CPU) is gone.

What is pinned, and why in these units: raw states/s on paxos-2 CANNOT be
monotone in batch size for ANY insert design — the workload's frontier is
a few thousand states wide, the engine pops fixed-size batches, and every
lane past the frontier is linear engine-wide padding waste (expand,
fingerprint, append — not insert work). The insert-side scaling shape IS
observable as LANE THROUGHPUT: popped lanes per second, i.e.
batch x max_actions x steps / time. A super-linear insert term makes lane
throughput FALL as batch grows (measured: the sort path degrades ~18%
from b=4096 to b=32768 on this box); the capped path must hold it
non-decreasing (within noise).

Golden parity for every capped variant rides along (the satellite's
correctness oracle: 2pc-4 = 8,258 generated / 1,568 unique).
"""

import pytest

from stateright_tpu.tensor.models import TensorTwoPhaseSys
from stateright_tpu.tensor.paxos import TensorPaxos
from stateright_tpu.tensor.resident import ResidentSearch

PAXOS2_GOLDEN = (32_971, 16_668)
TPC4_GOLDEN = (8_258, 1_568)

# Non-decreasing within 15% noise (the satellite's tolerance): each step up
# in batch may lose at most this factor of lane throughput.
NOISE = 0.85

BATCHES = (1024, 4096, 16384)


_searches: dict = {}
_measure_cache: dict = {}


def _lane_throughput(batch, variant, fresh=False):
    """(lanes/sec, states/sec) — warm-compiled, best of 2, memoized so the
    sweep and A/B tests share one compile+measure per config. `fresh=True`
    re-measures on the already-compiled engine (the flake-retry path: a
    transiently loaded CI box can corrupt one timing sample; a repeated
    SHAPE violation is the real signal)."""
    key = (batch, variant)
    if fresh or key not in _measure_cache:
        if key not in _searches:
            model = TensorPaxos(client_count=2)
            s = ResidentSearch(
                model, batch_size=batch, table_log2=16, insert_variant=variant
            )
            r = s.run()  # compile + warm-up
            assert (r.state_count, r.unique_state_count) == PAXOS2_GOLDEN, (
                batch, variant, r.state_count, r.unique_state_count,
            )
            _searches[key] = (s, r, batch * s.model.max_actions * r.steps)
        s, r, lanes = _searches[key]
        best = min(s.run().duration for _ in range(2))
        _measure_cache[key] = (lanes / best, r.state_count / best)
    return _measure_cache[key]


@pytest.mark.slow  # ~33s perf-monotonicity sweep: tier-2 (tier-1 is timeout-bound)
def test_capped_lane_throughput_non_decreasing_with_batch():
    # Compare the BEST observed throughput per batch across up to 3
    # measurement rounds: best-case timing reflects the algorithmic
    # per-step cost (the thing this test pins); one-off slow samples
    # reflect the shared CI box, not a regression.
    best = [0.0] * len(BATCHES)
    for attempt in range(3):
        for i, b in enumerate(BATCHES):
            best[i] = max(
                best[i], _lane_throughput(b, "capped", fresh=attempt > 0)[0]
            )
        if all(
            t_next >= t_prev * NOISE
            for t_prev, t_next in zip(best, best[1:])
        ):
            return
    raise AssertionError(
        "capped lane throughput fell with batch size (3 rounds): "
        + ", ".join(
            f"b={b}: {t:,.0f} lanes/s" for b, t in zip(BATCHES, best)
        )
        + " — the per-step cost is growing super-linearly again"
    )


@pytest.mark.slow  # ~23s measured A/B: tier-2 with its sweep sibling above
def test_capped_beats_sort_at_scale():
    # The A/B the capped path exists for: at a batch the sort term hurts,
    # capped must win outright (measured ~1.9x at b=4096 on the dev box;
    # asserted with a wide margin, and one re-measure, for noisy CI).
    for attempt in (0, 1):
        _, sps_sort = _lane_throughput(4096, "sort", fresh=attempt > 0)
        _, sps_capped = _lane_throughput(4096, "capped", fresh=attempt > 0)
        if sps_capped >= sps_sort * 1.2:
            return
    raise AssertionError(
        f"capped ({sps_capped:,.0f}/s) did not beat sort "
        f"({sps_sort:,.0f}/s) by 1.2x at batch 4096 (twice)"
    )


@pytest.mark.parametrize(
    "layout,variant",
    [
        ("split", "capped"),
        ("kv", "capped"),
        ("split", "capped-phased"),
    ],
)
def test_capped_variants_golden_parity_2pc4(layout, variant):
    r = ResidentSearch(
        TensorTwoPhaseSys(4),
        batch_size=512,
        table_log2=14,
        table_layout=layout,
        insert_variant=variant,
    ).run()
    assert (r.state_count, r.unique_state_count) == TPC4_GOLDEN
    assert r.complete


def test_frontier_engine_capped_golden_parity_2pc4():
    from stateright_tpu.tensor.frontier import FrontierSearch

    r = FrontierSearch(
        TensorTwoPhaseSys(4),
        batch_size=512,
        table_log2=14,
        insert_variant="capped",
    ).run()
    assert (r.state_count, r.unique_state_count) == TPC4_GOLDEN
    assert r.complete


# (The satellite's second oracle — paxos-2 = 32,971 / 16,668 — is asserted
# inside _lane_throughput for every batch of the monotonicity sweep.)
