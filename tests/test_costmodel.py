"""tensor/costmodel.py contract tests: the roofline model must (a) stay
anchored to the round-4 silicon measurement it was calibrated on, (b) keep
its layout constants in sync with the real hash table, and (c) predict the
structural properties the capped insert was built for — sort volume that
scales with new candidates, not batch. Pure host-side math: no jax."""

import math

import pytest

from stateright_tpu.tensor import costmodel as cm

# Round-4 anchor (r04 builder artifact): paxos-3 on a
# v5e — lanes 21, max_actions 14, batch 3072, table 2^22, split insert +
# DUS append, 12.9 ms/step.
ANCHOR = dict(lanes=21, max_actions=14, batch=3072, table_log2=22)
ANCHOR_MS = 12.9


def test_layout_constants_match_hashtable():
    from stateright_tpu.tensor import hashtable as ht

    assert cm.BUCKET == ht.BUCKET
    assert cm.KV_BUCKET == ht.KV_BUCKET
    assert cm.CLAIM_TILE == ht.CLAIM_TILE
    assert cm.CAP_MAX_TILES == ht.CAP_MAX_TILES


def test_reproduces_r4_paxos3_step_within_20pct():
    sc = cm.step_cost(**ANCHOR, variant="split", append="dus")
    assert abs(sc.total_ms - ANCHOR_MS) / ANCHOR_MS < 0.20, sc.total_ms
    # The breakdown must be a real decomposition, not a fudge total.
    assert math.isclose(sc.total_ms, sum(o.ms for o in sc.ops))
    assert math.isclose(sc.total_bytes, sum(o.bytes for o in sc.ops))
    assert all(o.bytes > 0 and o.ms > 0 for o in sc.ops)


def test_capped_sort_volume_scales_with_candidates_not_batch():
    # The tentpole claim: at fixed new-candidate fraction, the split sort
    # term grows as B log B while the capped sort term grows as
    # n_cand * log(tile) — so their ratio must widen with batch.
    def sort_bytes(variant, batch):
        sc = cm.step_cost(
            **{**ANCHOR, "batch": batch}, variant=variant, new_frac=0.1
        )
        return sum(o.bytes for o in sc.ops if o.name == "insert_sort")

    for batch in (4096, 32768):
        assert sort_bytes("capped", batch) < sort_bytes("split", batch)
    widen_small = sort_bytes("split", 4096) / sort_bytes("capped", 4096)
    widen_big = sort_bytes("split", 32768) / sort_bytes("capped", 32768)
    assert widen_big > widen_small


def test_capped_never_worse_than_split_even_when_batch_is_full():
    # At new_frac=1.0 (frontier fills every lane) the capped path gathers
    # the same rows as split but sorts T log T per tile instead of B log B;
    # the model must keep it within the cheap compaction term of split.
    # Allowed slop: the compaction pass, per-tile dispatch, and the
    # final tile's ceil-padding — all small by construction (<5%).
    full = cm.step_cost(**ANCHOR, variant="capped", new_frac=1.0)
    split = cm.step_cost(**ANCHOR, variant="split")
    assert full.total_ms <= split.total_ms * 1.05


def test_capped_cost_tracks_populated_lanes():
    # The padded-batch case the capped path exists for: halving the
    # populated fraction must shed a visible share of insert time.
    lo = cm.step_cost(**ANCHOR, variant="capped", new_frac=0.25)
    hi = cm.step_cost(**ANCHOR, variant="capped", new_frac=1.0)
    ins = lambda sc: sum(  # noqa: E731
        o.ms for o in sc.ops if o.name.startswith("insert_")
    )
    assert ins(lo) < 0.5 * ins(hi)


def test_kv_halves_probe_gather_bytes():
    g = lambda v: sum(  # noqa: E731
        o.bytes
        for o in cm.step_cost(**ANCHOR, variant=v).ops
        if o.name == "insert_gather"
    )
    assert g("kv") == g("split") / 2


def test_ranking_covers_all_variants_and_is_sorted():
    r = cm.predict_ranking(**ANCHOR, new_frac=0.35)
    assert {x["variant"] for x in r} == set(cm.INSERT_VARIANTS)
    assert [x["total_ms"] for x in r] == sorted(x["total_ms"] for x in r)
    assert all(x["insert_ms"] <= x["total_ms"] for x in r)


def test_bytes_per_state_and_hbm_frac():
    bps = cm.bytes_per_state(**ANCHOR, states_per_step=8000.0)
    assert bps > 0
    # r4 silicon: 627k states/s — the resulting effective-HBM fraction must
    # land in the 0.1-10% band the verdicts measured (order-of-magnitude
    # pin against unit slips in the byte accounting).
    frac = cm.hbm_frac(627_000.0, bps, cm.V5E)
    assert 0.001 < frac < 0.10, frac


def test_cpu_spec_exists_for_rehearsal_reporting():
    sc = cm.step_cost(**ANCHOR, variant="split", device=cm.CPU1)
    assert sc.total_ms > 0


@pytest.mark.parametrize(
    "device_kind,spec", [("TPU v5 lite", cm.V5E), ("cpu", cm.CPU1)]
)
def test_peaks_are_keyed_by_device_kind(device_kind, spec):
    assert cm.spec_for_device_kind(device_kind) is spec
    assert spec.peak_source


def test_unlisted_device_kind_gets_no_peaks():
    # A device the table does not list is an error — never the v5e peaks.
    with pytest.raises(ValueError, match="no cost-model peaks"):
        cm.spec_for_device_kind("TPU v4")


# -- tiered-store spill term ---------------------------------------------------


def test_r4_anchor_reproduces_within_1pct():
    # Regression pin for the spill-term addition: the calibrated model must
    # keep reproducing the round-4 silicon anchor within 1% — any change to
    # the shared terms that drifts the anchor shows up here, not on the
    # chip.
    sc = cm.step_cost(**ANCHOR, variant="split", append="dus")
    assert abs(sc.total_ms - ANCHOR_MS) / ANCHOR_MS < 0.01, sc.total_ms


def test_spill_none_is_byte_and_ms_identical():
    base = cm.step_cost(**ANCHOR, variant="split")
    off = cm.step_cost(**ANCHOR, variant="split", spill=None)
    assert base == off


def test_spill_term_adds_probe_and_eviction_ops():
    sc = cm.step_cost(
        **ANCHOR, variant="split",
        spill={"summary_hashes": 4, "evict_per_step": 500.0},
    )
    names = [o.name for o in sc.ops]
    assert "spill_probe" in names and "spill_evict" in names
    base = cm.step_cost(**ANCHOR, variant="split")
    assert sc.total_ms > base.total_ms
    assert sc.total_bytes > base.total_bytes
    # Probe cost scales with the hash count; eviction with the evict rate.
    k8 = cm.step_cost(
        **ANCHOR, variant="split", spill={"summary_hashes": 8}
    )
    k4 = cm.step_cost(
        **ANCHOR, variant="split", spill={"summary_hashes": 4}
    )
    probe = lambda s: next(o for o in s.ops if o.name == "spill_probe")  # noqa: E731
    assert probe(k8).bytes == 2 * probe(k4).bytes
    heavier = cm.step_cost(
        **ANCHOR, variant="split", spill={"evict_per_step": 1000.0}
    )
    lighter = cm.step_cost(
        **ANCHOR, variant="split", spill={"evict_per_step": 100.0}
    )
    assert heavier.total_ms > lighter.total_ms


def test_spill_term_composes_with_ranking():
    r = cm.predict_ranking(
        **ANCHOR, new_frac=0.35, spill={"summary_hashes": 4}
    )
    plain = cm.predict_ranking(**ANCHOR, new_frac=0.35)
    assert {x["variant"] for x in r} == set(cm.INSERT_VARIANTS)
    for with_spill, without in zip(
        sorted(r, key=lambda x: x["variant"]),
        sorted(plain, key=lambda x: x["variant"]),
    ):
        assert with_spill["total_ms"] > without["total_ms"]


def test_pallas_partition_mirror_matches_kernel():
    # costmodel stays jax-free, so the pallas partitioning formula is
    # restated, not imported — this pin is what keeps the two in sync
    # (same contract as test_layout_constants_match_hashtable).
    from stateright_tpu.tensor import pallas_hashtable as ph

    assert cm.PALLAS_ROW_ALIGN == ph.ROW_ALIGN
    assert cm.PALLAS_DEFAULT_PARTITIONS == ph.DEFAULT_PARTITIONS
    for log2 in (10, 12, 16, 20, 22, 27):
        assert cm.pallas_partition_count(1 << log2) == ph.pallas_partitions(
            1 << log2
        )


def test_pallas_term_scales_with_table_and_ranks_the_crossover():
    # The pallas kernel streams the whole partitioned table through VMEM
    # once per insert call, so — uniquely among the variants — its cost
    # must GROW with table_log2 at fixed batch, and the committed ranking
    # (predict_ranking) must flip from pallas to capped as the table
    # outgrows the batch.
    small = cm.step_cost(21, 14, 3072, 16, variant="pallas")
    big = cm.step_cost(21, 14, 3072, 22, variant="pallas")
    assert big.total_ms > small.total_ms
    stream = lambda s: next(  # noqa: E731
        o for o in s.ops if o.name == "insert_stream"
    )
    assert stream(big).bytes == 64 * stream(small).bytes  # 32*S exactly
    assert ("split", "pallas") in cm.ENGINE_VARIANTS
    assert "pallas" in cm.INSERT_VARIANTS

    def winner(table_log2, batch):
        r = cm.predict_ranking(
            21, 14, batch, table_log2, variants=("capped", "pallas")
        )
        return r[0]["variant"]

    assert winner(16, 3072) == "pallas"  # table fits: no claim phase wins
    assert winner(22, 3072) == "capped"  # r4 anchor: capped stays default
    assert winner(22, 131072) == "pallas"  # batch amortizes the stream


def test_sim_step_cost_structure_and_walks_prediction():
    # The fourth engine's walk-step term (ISSUE 14): trace-dedup pays the
    # per-lane cycle probe, shared-dedup swaps it for the ring scan plus
    # the SAME insert design the exhaustive engines race (at batch =
    # traces) — so the shared premium is exactly the priced insert ops.
    import pytest

    trace = cm.sim_step_cost(21, 14, 4096, dedup="trace")
    shared = cm.sim_step_cost(21, 14, 4096, dedup="shared", table_log2=22)
    assert trace.total_ms > 0 and shared.total_ms > trace.total_ms
    names_t = [o.name for o in trace.ops]
    names_s = [o.name for o in shared.ops]
    assert "cycle_probe" in names_t and "cycle_ring" not in names_t
    assert "cycle_ring" in names_s
    assert any(n.startswith("insert_") for n in names_s)
    assert not any(n.startswith("insert_") for n in names_t)
    # More lanes, more step cost; walks/s still grows with lanes because
    # every lane completes a walk every mean_walk_len steps (continuous
    # batching: no tail-idle correction needed).
    assert cm.sim_step_cost(21, 14, 8192).total_ms > trace.total_ms
    assert cm.sim_walks_per_sec(21, 14, 8192, 40.0) > cm.sim_walks_per_sec(
        21, 14, 4096, 40.0
    )
    with pytest.raises(ValueError):
        cm.sim_step_cost(21, 14, 4096, dedup="global")
