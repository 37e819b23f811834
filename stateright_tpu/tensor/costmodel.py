"""Roofline cost model for the device engine step.

Purpose: decide the split / kv / phased / capped insert race from COMMITTED
predictions instead of blind staging — chip time is budgeted, so every
chip run must race designs the model already ranked, and every surprise
must become a calibration update.

Anchor measurement (round-4 silicon, v5e, paxos-3: lanes=21, max_actions=14,
batch 3072, table 2^22, split sort-claim insert + DUS append): 12.9 ms/step
at 627k states/s, with the xplane attribution (r04 builder artifact):

    fusion.1137 (expand + fingerprint + props + append)   5.77 ms
    while.95    (insert: 4-op sort + bucket gathers + claim)  4.75 ms
    everything else (pop, compact, counters, masks)       ~2.4 ms

The per-op-class achieved bandwidths below are FIT to that attribution and
sit far below the v5e's 819 GB/s peak on purpose: rounds 4-5 measured the
engine at 1-2% effective HBM bandwidth, and the model's job is to
extrapolate from the machine that was measured, not the machine the spec
sheet promises. The VALUE of the model is the scaling structure — how each
term moves with batch, table size, lane count, and the new-candidate
fraction — which is what ranks the variants; absolute times are anchored
but soft.

This module is deliberately pure Python (no jax import): it must be usable
from bench.py's host side, the tuner, and tests without touching a backend.
Keep the layout constants in sync with tensor/hashtable.py (asserted by
tests/test_costmodel.py).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import NamedTuple, Optional

from ..knobs import COST_VARIANTS, SIM_DEDUP_KINDS

# Mirrors of tensor/hashtable.py layout constants (pinned by test).
BUCKET = 128
KV_BUCKET = 64
CLAIM_TILE = 4096
CAP_MAX_TILES = 64
BUCKET_ROW_BYTES = BUCKET * 4  # one gathered bucket row (512 B)

# Sort operand counts: the hoisted round-1 sort is 3 u32 operands
# (rotr-packed key, lo, iota — hashtable._insert_impl round-5 shape); the
# overflow-loop sort is 4 but runs ~zero iterations at sane load factors.
SORT_OPERANDS = 3

# The cost-variant alphabet lives in the one knob registry
# (stateright_tpu/knobs.py COST_VARIANTS); re-exported under the name this
# module has always used.
INSERT_VARIANTS = COST_VARIANTS

# (table_layout, insert_variant) engine options -> cost-model variant name.
# The single source of truth for this mapping: bench.py's roofline
# annotation and scripts/tpu_tune.py's predicted_ms both read it, so a new
# engine variant only needs a row here to be costed everywhere.
ENGINE_VARIANTS = {
    ("split", "sort"): "split",
    ("kv", "sort"): "kv",
    ("split", "phased"): "phased",
    ("split", "capped"): "capped",
    ("kv", "capped"): "capped-kv",
    ("split", "capped-phased"): "capped",
    ("split", "pallas"): "pallas",
}

# Mirrors of tensor/pallas_hashtable.py partitioning constants (pinned by
# tests/test_costmodel.py — this module stays jax-free, so the formula is
# restated, not imported).
PALLAS_ROW_ALIGN = 1024
PALLAS_DEFAULT_PARTITIONS = 64


def pallas_partition_count(table_slots: int) -> int:
    """pallas_hashtable.pallas_partitions without the jax import."""
    return max(
        1, min(PALLAS_DEFAULT_PARTITIONS, table_slots // PALLAS_ROW_ALIGN)
    )


@dataclass(frozen=True)
class DeviceSpec:
    """Peak numbers plus ACHIEVED per-op-class rates (calibrated, see module
    docstring). `hbm_gbps` is the roofline peak used for hbm_frac; the
    gbps_* rates are what this engine actually sustains per op class."""

    name: str
    hbm_gbps: float  # peak HBM bandwidth (roofline denominator)
    gbps_gather: float  # [B, 128] bucket-row gathers
    gbps_sort: float  # lax.sort, per operand-byte per pass-equivalent
    gbps_scatter: float  # claim/unsort scatters + readbacks
    gbps_stream: float  # contiguous DUS/compaction traffic
    ns_expand_elem: float  # expand+fingerprint+props fusion, per succ lane
    ns_other_lane: float  # pop/masks/counters residue, per flat succ lane
    ms_dispatch: float  # per serialized probe round / claim tile
    # Host link for the tiered store's eviction traffic (device-to-host
    # window pulls + spilled fingerprints). Uncalibrated default: no spill
    # event has run on a chip yet; the first tiered chip run anchors it.
    pcie_gbps: float = 12.0
    peak_source: str = ""  # where hbm_gbps comes from


# Fit to the r4 anchor (see module docstring); the split prediction for the
# anchor config must stay within ~20% of 12.9 ms (tests/test_costmodel.py).
V5E = DeviceSpec(
    name="tpu-v5e",
    hbm_gbps=819.0,
    peak_source='Google Cloud documentation, "TPU v5e": 16 GB HBM at 819 GB/s',
    gbps_gather=15.0,
    gbps_sort=8.0,
    gbps_scatter=3.0,
    gbps_stream=20.0,
    ns_expand_elem=6.15,
    ns_other_lane=55.8,
    ms_dispatch=0.01,
)

# Round-4 silicon: the row-scatter queue append moved ~2.4 GiB/s effective
# (44.7% of the paxos-3 step before the DUS form replaced it).
GBPS_APPEND_SCATTER = 2.6

# One CPU core of the rehearsal box, roughed in from the r4 CPU sweeps
# (paxos-3 b=32768 ~101k gen/s; no per-op attribution exists, so treat CPU
# *times* as low-confidence — CPU *bytes* are exact and are what
# cpu_bytes_per_state reports).
CPU1 = DeviceSpec(
    name="cpu-1core",
    hbm_gbps=12.0,
    peak_source="rough estimate from the round-4 CPU sweeps; not published",
    gbps_gather=4.0,
    gbps_sort=0.8,
    gbps_scatter=2.0,
    gbps_stream=6.0,
    ns_expand_elem=15.0,
    ns_other_lane=80.0,
    ms_dispatch=0.05,
)


# -- calibration overlay (obs/calib.py fits it; this module only loads) ----
#: Env var naming a fitted-overlay JSON ({"base": <kind>, "rates": {...}})
#: written by `tpu_tune --calibrate`. Loading yields a NEW DeviceSpec — the
#: committed V5E/CPU1 anchors are never mutated, so the r4 anchor pin holds
#: with or without an overlay active.
CALIB_ENV = "SR_TPU_COSTMODEL_CALIB"

#: THE table of committed specs, keyed by `jax.devices()[0].device_kind`
#: (each spec's `peak_source` names where its peak comes from). A kind not
#: listed has no numbers here: `spec_for_device_kind` raises, so no device
#: is ever costed with another device's peaks.
BY_DEVICE_KIND = {"TPU v5 lite": V5E, "cpu": CPU1}

#: The same specs by DeviceSpec.name — the key calibration records and
#: overlays carry.
DEVICE_KINDS = {s.name: s for s in BY_DEVICE_KIND.values()}


def spec_for_device_kind(device_kind: str) -> "DeviceSpec":
    """The committed spec for a jax `device_kind` string; ValueError for a
    kind the table does not list."""
    try:
        return BY_DEVICE_KIND[device_kind]
    except KeyError:
        raise ValueError(
            f"no cost-model peaks for device kind {device_kind!r}; "
            f"known: {sorted(BY_DEVICE_KIND)} (tensor/costmodel.py)"
        ) from None


def stock_device(kind: str) -> "DeviceSpec":
    """The committed spec for a device-kind name ("tpu-v5e" | "cpu-1core")."""
    try:
        return DEVICE_KINDS[kind]
    except KeyError:
        raise ValueError(
            f"unknown device kind {kind!r}; known: {sorted(DEVICE_KINDS)}"
        )


_CALIB_CACHE: dict = {}  # path -> (mtime, DeviceSpec)


def load_calibration(path: Optional[str] = None) -> Optional["DeviceSpec"]:
    """The fitted-overlay DeviceSpec from `path` (default: $CALIB_ENV), or
    None when no overlay is configured/readable. The returned spec keeps
    the base kind's `name` and `hbm_gbps` (roofline denominator) and
    overrides only the achieved rates present in the overlay's "rates"
    dict — a NEW instance every load path; stock specs stay frozen."""
    import json
    import os

    path = path or os.environ.get(CALIB_ENV)
    if not path:
        return None
    try:
        mtime = os.path.getmtime(path)
        hit = _CALIB_CACHE.get(path)
        if hit is not None and hit[0] == mtime:
            return hit[1]
        with open(path, "r") as f:
            doc = json.load(f)
        base = stock_device(doc["base"])
        rates = doc.get("rates") or {}
        fields = {
            k: float(v) for k, v in rates.items()
            if k in DeviceSpec.__dataclass_fields__ and float(v) > 0
        }
        from dataclasses import replace

        spec = replace(base, **fields)
    except (OSError, ValueError, KeyError, TypeError):
        return None
    _CALIB_CACHE[path] = (mtime, spec)
    return spec


class OpCost(NamedTuple):
    name: str
    bytes: float  # HBM bytes touched
    ms: float  # predicted time at the calibrated achieved rate


class StepCost(NamedTuple):
    total_ms: float
    total_bytes: float  # roofline numerator for hbm_frac
    ops: tuple  # OpCost rows, the per-op breakdown


def _ms(nbytes: float, gbps: float) -> float:
    return nbytes / (gbps * 1e9) * 1e3


SPILL_ENTRY_BYTES = 16  # (lo, hi, parent_lo, parent_hi) per evicted slot


def step_cost(
    lanes: int,
    max_actions: int,
    batch: int,
    table_log2: int,
    *,
    variant: str = "split",
    append: str = "dus",
    new_frac: float = 0.5,
    phased_rounds: float = 3.9,
    tile: int = CLAIM_TILE,
    device: DeviceSpec = V5E,
    spill: Optional[dict] = None,
) -> StepCost:
    """Predict one engine step for an insert `variant` (INSERT_VARIANTS).

    `new_frac` is the fraction of the B = batch x max_actions flat successor
    lanes the capped path must tile over — the POPULATED lanes (active and
    in-boundary; padding on sub-batch frontiers is compacted away before
    any tile runs). Estimate it as generated-states-per-step / B from a
    run, or 1.0 for a frontier that fills the batch. It only moves the
    capped variants.

    `phased_rounds` is the average serialized probe-round count of the
    phased scatter-max insert (r4 silicon measured ~3.9 on paxos-3).

    `table_log2` is DELIBERATELY inert for the XLA variants: per-lane probe
    traffic is one fixed 512-byte bucket row regardless of table size, and
    chain-overflow rounds are ~zero at sane load factors, so table size
    only matters through load factor — a term the r4 anchor cannot
    calibrate. The PALLAS variant is the exception: its kernel streams the
    whole partitioned table through VMEM once per insert call, so its
    `insert_stream` term scales directly with 2^table_log2 (the ranking
    lever — see the variant branch below).

    `spill` (None = plain device store; the None path is byte- and
    ms-identical to the pre-tiered model, pinned by the 1% anchor
    regression in tests/test_costmodel.py) models the tiered store's two
    device-side costs:
    - the per-step Bloom SUMMARY PROBE: `summary_hashes` (default 4) word
      gathers per flat successor lane, at the gather rate;
    - amortized EVICTION traffic: `evict_per_step` states/step crossing
      PCIe (window pull + spilled entries, 2x SPILL_ENTRY_BYTES each) plus
      the zeroed-window write-back at the stream rate.
    Host-side suspect resolution is deliberately NOT a device term: it
    overlaps the next dispatch on the host thread.
    """
    if variant not in INSERT_VARIANTS:
        raise ValueError(
            f"variant must be one of {INSERT_VARIANTS}, got {variant!r}"
        )
    K, A, L = batch, max_actions, lanes
    B = K * A
    ops = []

    # -- expand + fingerprint + property masks (the mega-fusion) ---------------
    expand_bytes = 4 * (K * L + 2 * B * L)
    ops.append(OpCost("expand_fuse", expand_bytes, B * L * device.ns_expand_elem * 1e-6))

    # -- visited-set insert, per variant ---------------------------------------
    log2_b = math.log2(max(B, 2))
    sort_bytes_full = SORT_OPERANDS * 4 * B * log2_b
    gather_lanes = 1 if variant in ("kv", "capped-kv") else 2
    gathers_full = gather_lanes * B * BUCKET_ROW_BYTES
    claim_misc_full = 8 * B * 4  # table scatters + unsort iota + readbacks

    if variant in ("split", "kv"):
        ops.append(OpCost("insert_sort", sort_bytes_full, _ms(sort_bytes_full, device.gbps_sort)))
        ops.append(OpCost("insert_gather", gathers_full, _ms(gathers_full, device.gbps_gather)))
        ops.append(OpCost("insert_claim", claim_misc_full, _ms(claim_misc_full, device.gbps_scatter) + device.ms_dispatch))
    elif variant == "phased":
        # No sort; `phased_rounds` serialized rounds, each a full-width
        # bucket gather + 3 scatter-max phases with readback gets.
        per_round_scatter = 16 * B * 4
        ops.append(OpCost(
            "insert_gather",
            phased_rounds * gathers_full,
            phased_rounds * _ms(gathers_full, device.gbps_gather),
        ))
        ops.append(OpCost(
            "insert_claim",
            phased_rounds * per_round_scatter,
            phased_rounds * (_ms(per_round_scatter, device.gbps_scatter) + device.ms_dispatch),
        ))
    elif variant == "pallas":
        # Route-then-probe (tensor/pallas_hashtable.py): ONE stable sort of
        # the batch by partition id (2 u32 operands: packed pid + iota)
        # replaces the sort-claim phase entirely; the kernel then streams
        # EVERY partition through VMEM once per insert call — a read+write
        # of all four table arrays, the table-size term no XLA variant has
        # (their per-lane probe traffic is one bucket row regardless of
        # table size). In-partition probes run serially at VMEM speed
        # (~free next to the HBM terms); the per-partition grid step is
        # not, and neither are the routing scatter-pack and the verdict
        # un-route. This is why the committed prediction ranks pallas by
        # the table:batch ratio — it wins only when the routed batch
        # amortizes the full-table round trip.
        S = 1 << table_log2
        n_parts = pallas_partition_count(S)
        route_sort = 2 * 4 * B * log2_b
        part_stream = 2 * 4 * S * 4  # 4 u32 arrays in + out of VMEM
        pack_bytes = 10 * B * 4  # route scatter-pack + verdict un-route
        ops.append(OpCost(
            "insert_sort", route_sort, _ms(route_sort, device.gbps_sort)
        ))
        ops.append(OpCost(
            "insert_stream", part_stream,
            _ms(part_stream, device.gbps_stream),
        ))
        ops.append(OpCost(
            "insert_claim", pack_bytes,
            _ms(pack_bytes, device.gbps_scatter)
            + n_parts * device.ms_dispatch,
        ))
    else:  # capped / capped-kv: active-compaction + claim tiles
        pow2_b = 1 << max(int(B) - 1, 1).bit_length()
        T = min(pow2_b, max(tile, pow2_b // CAP_MAX_TILES))
        n_tiles = max(math.ceil(new_frac * B / T), 0)
        compact = 10 * B * 4  # 5 u32 arrays, read+write, cumsum-scatter
        tile_sort = n_tiles * SORT_OPERANDS * 4 * T * math.log2(max(T, 2))
        tile_gather = n_tiles * gather_lanes * T * BUCKET_ROW_BYTES
        tile_claim = n_tiles * 8 * T * 4
        ops.append(OpCost("insert_compact", compact, _ms(compact, device.gbps_stream)))
        ops.append(OpCost("insert_sort", tile_sort, _ms(tile_sort, device.gbps_sort)))
        ops.append(OpCost("insert_gather", tile_gather, _ms(tile_gather, device.gbps_gather)))
        ops.append(OpCost(
            "insert_claim", tile_claim,
            _ms(tile_claim, device.gbps_scatter) + n_tiles * device.ms_dispatch,
        ))

    # -- queue append ----------------------------------------------------------
    append_bytes = 2 * 4 * (L + 4) * B  # compaction build + block write
    append_gbps = device.gbps_stream if append == "dus" else GBPS_APPEND_SCATTER
    ops.append(OpCost("append", append_bytes, _ms(append_bytes, append_gbps)))

    # -- tiered store: summary probe + amortized eviction ----------------------
    if spill is not None:
        if variant == "pallas":
            # The fused kernel probes the summary INSIDE its partition pass
            # (no separate maybe_contains gather sweep): the word array
            # rides into VMEM once per partition, so the probe cost is the
            # grid-replicated summary stream, not k gathers per lane.
            slog2 = int(spill.get("summary_log2", 20))
            n_parts = pallas_partition_count(1 << table_log2)
            # The kernel pads the word array to a tile-aligned block
            # (>= ROW_ALIGN words) and streams the WHOLE padded block per
            # grid step — small summaries still pay the padded size.
            probe_bytes = n_parts * max(
                PALLAS_ROW_ALIGN * 4, (1 << slog2) // 8
            )
            ops.append(OpCost(
                "spill_probe", probe_bytes,
                _ms(probe_bytes, device.gbps_stream),
            ))
        else:
            hashes = int(spill.get("summary_hashes", 4))
            probe_bytes = hashes * B * 4  # k word gathers per flat lane
            ops.append(OpCost(
                "spill_probe", probe_bytes,
                _ms(probe_bytes, device.gbps_gather),
            ))
        evict_per_step = float(spill.get("evict_per_step", 0.0))
        if evict_per_step > 0:
            pcie_bytes = evict_per_step * 2 * SPILL_ENTRY_BYTES
            wb_bytes = evict_per_step * SPILL_ENTRY_BYTES
            ops.append(OpCost(
                "spill_evict",
                pcie_bytes + wb_bytes,
                _ms(pcie_bytes, device.pcie_gbps)
                + _ms(wb_bytes, device.gbps_stream),
            ))

    # -- pop / counters / discovery residue ------------------------------------
    other_bytes = 4 * (L + 4) * B
    ops.append(OpCost("other", other_bytes, B * device.ns_other_lane * 1e-6))

    return StepCost(
        total_ms=sum(o.ms for o in ops),
        total_bytes=sum(o.bytes for o in ops),
        ops=tuple(ops),
    )


def bytes_per_state(
    lanes: int,
    max_actions: int,
    batch: int,
    table_log2: int,
    states_per_step: float,
    *,
    variant: str = "split",
    append: str = "dus",
    new_frac: float = 0.5,
    device: DeviceSpec = V5E,
    spill: Optional[dict] = None,
) -> float:
    """HBM bytes touched per GENERATED state: the step's modeled byte total
    over the measured states-per-step (state_count / steps from a run)."""
    sc = step_cost(
        lanes, max_actions, batch, table_log2,
        variant=variant, append=append, new_frac=new_frac, device=device,
        spill=spill,
    )
    return sc.total_bytes / max(states_per_step, 1e-9)


def hbm_frac(
    states_per_sec: float,
    bytes_per_state_: float,
    device: DeviceSpec,
) -> float:
    """Effective HBM fraction — the MFU analogue this engine is judged on
    (~1-2% in rounds 4-5): modeled bytes moved per second over peak."""
    return states_per_sec * bytes_per_state_ / (device.hbm_gbps * 1e9)


def sim_step_cost(
    lanes: int,
    max_actions: int,
    traces: int,
    *,
    dedup: str = "trace",
    cycle_log2: int = 9,
    ring: int = 64,
    table_log2: int = 20,
    variant: str = "capped",
    device: DeviceSpec = V5E,
) -> StepCost:
    """Predict one device-simulation walk step (tensor/simulation.py): all
    `traces` lanes evaluate properties, detect cycles, and step at once.

    The structure is the frontier step minus the queue plane (walks carry
    no frontier; the per-lane path append is a contiguous column write)
    plus the cycle-detection term the exhaustive engines do not have:

    - ``dedup="trace"``: the per-lane generation-stamped cycle table — an
      expected ~2 serialized probe rounds of one-slot gathers across three
      [T, 2^cycle_log2] arrays (random access, gather rate).
    - ``dedup="shared"``: the per-walk ring scan (3 contiguous [T, ring]
      arrays, stream rate) plus the shared-table insert — the same
      tensor/inserts.py design the exhaustive engines run, priced by the
      existing `step_cost` insert branch at batch = traces x 1 flat lane.

    Walks/s for a workload follows as traces / (mean_walk_len x step_time)
    (`sim_walks_per_sec`); with continuous walk batching the lanes stay
    full, so the prediction needs no tail-idle correction — that is the
    point of the design.
    """
    if dedup not in SIM_DEDUP_KINDS:  # knob universe: knobs.py
        raise ValueError(
            f"dedup must be one of {SIM_DEDUP_KINDS}, got {dedup!r}"
        )
    T, A, L = traces, max_actions, lanes
    B = T * A
    ops = []

    # expand + fingerprint + property masks (same mega-fusion shape).
    expand_bytes = 4 * (T * L + 2 * B * L)
    ops.append(OpCost(
        "expand_fuse", expand_bytes, B * L * device.ns_expand_elem * 1e-6
    ))

    # uniform successor choice: per-lane RNG fold-in + cumsum/argmax pick.
    choose_bytes = 8 * B * 4
    ops.append(OpCost(
        "walk_choose", choose_bytes, _ms(choose_bytes, device.gbps_stream)
    ))

    if dedup == "trace":
        # ~2 serialized probe rounds, one random slot per lane per round
        # across (lo, hi, gen); each round is a dispatch.
        probe_rounds = 2.0
        probe_bytes = probe_rounds * 3 * T * 4
        ops.append(OpCost(
            "cycle_probe", probe_bytes,
            _ms(probe_bytes, device.gbps_gather)
            + probe_rounds * device.ms_dispatch,
        ))
    else:
        ring_bytes = 3 * T * ring * 4
        ops.append(OpCost(
            "cycle_ring", ring_bytes, _ms(ring_bytes, device.gbps_stream)
        ))
        # The shared-table insert at batch = traces (one fp per lane per
        # step): the SAME priced design the exhaustive engines run.
        insert = step_cost(
            lanes, 1, traces, table_log2, variant=variant, device=device
        )
        for op in insert.ops:
            if op.name.startswith("insert_"):
                ops.append(op)

    # path append (contiguous column write) + ending/restart residue.
    other_bytes = 4 * (L + 6) * T
    ops.append(OpCost("other", other_bytes, T * device.ns_other_lane * 1e-6))

    return StepCost(
        total_ms=sum(o.ms for o in ops),
        total_bytes=sum(o.bytes for o in ops),
        ops=tuple(ops),
    )


def sim_walks_per_sec(
    lanes: int,
    max_actions: int,
    traces: int,
    mean_walk_len: float,
    *,
    dedup: str = "trace",
    device: DeviceSpec = V5E,
    **kw,
) -> float:
    """Committed walks/s prediction: with continuous batching every lane
    completes a walk every `mean_walk_len` steps, so throughput is
    traces / (mean_walk_len x step_time)."""
    sc = sim_step_cost(
        lanes, max_actions, traces, dedup=dedup, device=device, **kw
    )
    return traces / (max(mean_walk_len, 1.0) * sc.total_ms * 1e-3)


def predict_ranking(
    lanes: int,
    max_actions: int,
    batch: int,
    table_log2: int,
    *,
    new_frac: float = 0.5,
    append: str = "dus",
    device: DeviceSpec = V5E,
    variants: Optional[tuple] = None,
    spill: Optional[dict] = None,
) -> list:
    """Rank insert variants by predicted step time (fastest first). Returns
    [{"variant", "total_ms", "insert_ms", "bytes"}...] — the committed
    prediction format the tuner's ranking JSON uses."""
    out = []
    for v in variants or INSERT_VARIANTS:
        sc = step_cost(
            lanes, max_actions, batch, table_log2,
            variant=v, append=append, new_frac=new_frac, device=device,
            spill=spill,
        )
        out.append({
            "variant": v,
            "total_ms": round(sc.total_ms, 3),
            "insert_ms": round(
                sum(o.ms for o in sc.ops if o.name.startswith("insert_")), 3
            ),
            "bytes": int(sc.total_bytes),
        })
    return sorted(out, key=lambda r: r["total_ms"])
