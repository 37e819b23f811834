"""Device-side step telemetry: a fixed-width metrics row per engine step,
accumulated in a device-resident ring buffer and drained to host in bulk.

The design constraint is the hot path: the resident/sharded engines run their
whole search inside one `lax.while_loop`, so ANY per-step host involvement
would serialize the loop on the host round trip. The ring sidesteps that —
each loop iteration scatters one `uint32[len(STEP_COLS)]` row at
`steps % capacity` into a carry-resident buffer (a ~32-byte write next to the
megabytes the step already moves), and the host reads the whole ring ONLY at
boundaries where it already holds control and has already synced (chunk
returns, run end). Zero added per-step syncs; transfer cost amortizes over
the chunk's thousands of steps.

Host-orchestrated layers (FrontierSearch, the check service's ServiceEngine)
already fetch every per-step scalar the row needs, so they append host-side
rows directly — same schema, exact per-step wall times included.

`StepRing` is the host half: it owns the drained rows, exact running totals
(kept even when old rows fall off the ring), per-drain step timing, and the
`summary()` the engines surface as `SearchResult.detail["telemetry"]`.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

#: The one fixed row schema every engine's telemetry step emits, in column
#: order. All columns are uint32 on device.
#:
#: step          global step index (the ring write position is step % capacity)
#: active        populated frontier lanes this step (batch occupancy)
#: generated     post-boundary, pre-dedup successors this step
#: claimed       fresh visited-table claims this step (enqueued + suspects)
#: queue_len     frontier queue occupancy after the step (tail - head)
#: table_claims  cumulative occupied table slots (fill = claims / table size)
#: suspects      suspect-buffer occupancy (tiered store; 0 otherwise)
#: depth         max BFS depth reached so far
STEP_COLS = (
    "step",
    "active",
    "generated",
    "claimed",
    "queue_len",
    "table_claims",
    "suspects",
    "depth",
)

N_COLS = len(STEP_COLS)
_I = {name: i for i, name in enumerate(STEP_COLS)}


def _pcts(values: np.ndarray) -> dict:
    """{mean, p50, p95, max} of a column — the histogram digest the bench
    rows and /metrics carry (full histograms would bloat the one-line JSON)."""
    if values.size == 0:
        return {"mean": 0.0, "p50": 0.0, "p95": 0.0, "max": 0.0}
    v = values.astype(np.float64)
    return {
        "mean": round(float(v.mean()), 2),
        "p50": round(float(np.percentile(v, 50)), 2),
        "p95": round(float(np.percentile(v, 95)), 2),
        "max": float(v.max()),
    }


def _pcts_weighted(pairs: list) -> dict:
    """`_pcts` over (count, value) pairs without materializing count-many
    copies — the device rings only know per-chunk step-time averages, and a
    long run can hold thousands of chunks of thousands of steps each."""
    if not pairs:
        return {"mean": 0.0, "p50": 0.0, "p95": 0.0, "max": 0.0}
    counts = np.asarray([c for c, _ in pairs], dtype=np.float64)
    vals = np.asarray([v for _, v in pairs], dtype=np.float64)
    order = np.argsort(vals)
    vals, counts = vals[order], counts[order]
    cum = np.cumsum(counts)
    total = cum[-1]

    def q(p: float) -> float:
        i = int(np.searchsorted(cum, p * total, side="left"))
        return float(vals[min(i, len(vals) - 1)])

    return {
        "mean": round(float((vals * counts).sum() / total), 2),
        "p50": round(q(0.5), 2),
        "p95": round(q(0.95), 2),
        "max": float(vals.max()),
    }


class StepRing:
    """Host accumulator over the fixed-width step rows.

    Rows arrive either one at a time (`append`, host-orchestrated engines —
    exact, with per-step wall time) or in bulk (`drain`/`drain_sharded`,
    device rings). Retention is capped at `capacity` rows (oldest dropped,
    counted in `dropped_steps`); the running TOTALS (`steps`,
    `generated_total`, `claimed_total`) stay exact for appended rows and for
    every drained row that was still resident in the device ring.

    `tile`: the rows of one queue-append tile of a device engine that
    appends by tiles (`append_new_dus`; 0 otherwise). Each drained row then
    adds ceil(claimed / tile) to `append_tiles_total` (per shard row on a
    mesh, since each chip writes its own tiles), computed here on the host
    and never on the device. Steps that fell off the device ring before a
    drain (`dropped_steps`) are not counted, and in a tiered run the
    suspect buffer's own tiles are not told apart from the queue's.
    """

    def __init__(self, capacity: int = 4096, tile: int = 0):
        self.capacity = max(int(capacity), 1)
        self.tile = int(tile)
        self.append_tiles_total = 0
        self._rows: list[np.ndarray] = []  # uint32[N_COLS] each
        self._times_us: list[float] = []  # per-step wall times (host engines)
        self._chunk_times: list[tuple[int, float]] = []  # (steps, avg_us)
        self.steps = 0
        self.dropped_steps = 0
        self.generated_total = 0
        self.claimed_total = 0
        self._drained = 0  # device-ring drain watermark (step index)
        self.per_shard_claimed: Optional[np.ndarray] = None

    def fresh(self) -> "StepRing":
        """A new empty ring with the same capacity (engines start one per
        search so resumed runs keep accumulating and fresh runs do not)."""
        return StepRing(self.capacity, self.tile)

    def skip_to(self, steps: int) -> None:
        """Mark steps [0, steps) as having happened elsewhere (checkpoint
        restore): they count toward `steps` but were never captured."""
        self.steps = self.dropped_steps = self._drained = int(steps)

    def note_uncaptured(self, n: int = 1) -> None:
        """Count `n` steps that ran but whose row was never recorded (e.g.
        a host engine's early-exit step, whose contribution the search
        itself discards) — keeps `steps` equal to the engine's step count
        while `dropped_steps` marks the digest as partial."""
        self.steps += n
        self.dropped_steps += n
        self._drained += n

    # -- host-side appends (frontier / service engines) ------------------------

    def append(
        self,
        active: int,
        generated: int,
        claimed: int,
        queue_len: int,
        table_claims: int,
        suspects: int = 0,
        depth: int = 0,
        step_us: Optional[float] = None,
    ) -> None:
        row = np.asarray(
            [
                self.steps, active, generated, claimed,
                queue_len, table_claims, suspects, depth,
            ],
            dtype=np.uint32,
        )
        self._push(row)
        self.steps += 1
        self.generated_total += int(generated)
        self.claimed_total += int(claimed)
        if step_us is not None:
            self._times_us.append(float(step_us))
            if len(self._times_us) > self.capacity:
                del self._times_us[: -self.capacity]

    def _push(self, row: np.ndarray) -> None:
        self._rows.append(row)
        if len(self._rows) > self.capacity:
            drop = len(self._rows) - self.capacity
            self.dropped_steps += drop
            del self._rows[:drop]

    def _extend(self, rows: np.ndarray) -> None:
        self._rows.extend(rows)
        if len(self._rows) > self.capacity:
            drop = len(self._rows) - self.capacity
            self.dropped_steps += drop
            del self._rows[:drop]

    # -- device-ring drains ----------------------------------------------------

    def _count_tiles(self, claimed: np.ndarray) -> None:
        if self.tile:
            c = claimed.astype(np.int64)
            self.append_tiles_total += int(((c + self.tile - 1) // self.tile).sum())

    def drain(
        self,
        ring: np.ndarray,
        steps_total: int,
        window_us: Optional[float] = None,
    ) -> int:
        """Fold the device ring (`uint32[capacity, N_COLS]`, row for step i at
        i % capacity) into the host state. `steps_total` is the engine's step
        counter at this boundary; rows since the last drain that were already
        overwritten on device count as dropped. `window_us` is the wall time
        of the drained window (per-step times become the window average).
        Returns the number of rows captured."""
        steps_total = int(steps_total)
        if steps_total < self._drained:
            # The engine restarted its step counter under us (fresh search on
            # a reused ring): start over rather than mis-slice.
            self.__init__(self.capacity, self.tile)
        new = steps_total - self._drained
        if new <= 0:
            return 0
        R = ring.shape[0] if ring.ndim == 2 else 0
        if R == 0:  # telemetry ring disabled on device: count, capture nothing
            self.dropped_steps += new
            self.steps = self._drained = steps_total
            return 0
        first = max(self._drained, steps_total - R)
        self.dropped_steps += first - self._drained
        # One gather-COPY (never views into `ring`: retaining views would
        # pin each chunk's whole transferred buffer for the ring lifetime).
        idx = np.arange(first, steps_total, dtype=np.int64) % R
        rows = np.ascontiguousarray(ring[idx])
        self.generated_total += int(rows[:, _I["generated"]].sum())
        self.claimed_total += int(rows[:, _I["claimed"]].sum())
        self._count_tiles(rows[:, _I["claimed"]])
        self._extend(rows)
        self.steps = steps_total
        self._drained = steps_total
        if window_us is not None and new > 0:
            self._chunk_times.append((new, float(window_us) / new))
            if len(self._chunk_times) > self.capacity:
                del self._chunk_times[: -self.capacity]
        return steps_total - first

    def drain_sharded(
        self,
        rings: np.ndarray,
        steps_total: int,
        window_us: Optional[float] = None,
    ) -> int:
        """Drain per-shard rings (`uint32[n_shards, capacity, N_COLS]`) whose
        step counters are globally synced: per step, extensive columns
        (active/generated/claimed/queue_len/suspects) sum across shards while
        table_claims and depth take the max (fill and depth are per-shard
        maxima — the balance question is "how hot is the hottest shard").
        Also accumulates per-shard claimed totals for the imbalance digest."""
        steps_total = int(steps_total)
        if steps_total < self._drained:
            self.__init__(self.capacity, self.tile)
        N = rings.shape[0]
        if self.per_shard_claimed is None:
            self.per_shard_claimed = np.zeros(N, dtype=np.int64)
        new = steps_total - self._drained
        if new <= 0:
            return 0
        R = rings.shape[1] if rings.ndim == 3 else 0
        if R == 0:
            self.dropped_steps += new
            self.steps = self._drained = steps_total
            return 0
        first = max(self._drained, steps_total - R)
        self.dropped_steps += first - self._drained
        sum_cols = [_I[c] for c in
                    ("active", "generated", "claimed", "queue_len", "suspects")]
        max_cols = [_I["table_claims"], _I["depth"]]
        # Vectorized gather-COPY over the window (no views into `rings`).
        steps_idx = np.arange(first, steps_total, dtype=np.int64)
        shard_rows = rings[:, steps_idx % R, :].astype(np.int64)  # [N, n, C]
        rows = np.zeros((len(steps_idx), N_COLS), dtype=np.uint32)
        rows[:, _I["step"]] = steps_idx.astype(np.uint32)
        for c in sum_cols:
            rows[:, c] = np.minimum(
                shard_rows[:, :, c].sum(axis=0), 0xFFFFFFFF
            ).astype(np.uint32)
        for c in max_cols:
            rows[:, c] = shard_rows[:, :, c].max(axis=0).astype(np.uint32)
        self.generated_total += int(shard_rows[:, :, _I["generated"]].sum())
        self.claimed_total += int(shard_rows[:, :, _I["claimed"]].sum())
        self.per_shard_claimed += shard_rows[:, :, _I["claimed"]].sum(axis=1)
        self._count_tiles(shard_rows[:, :, _I["claimed"]])
        self._extend(rows)
        self.steps = steps_total
        self._drained = steps_total
        if window_us is not None and new > 0:
            self._chunk_times.append((new, float(window_us) / new))
            if len(self._chunk_times) > self.capacity:
                del self._chunk_times[: -self.capacity]
        return steps_total - first

    # -- summary ---------------------------------------------------------------

    def _col(self, name: str) -> np.ndarray:
        if not self._rows:
            return np.zeros(0, dtype=np.uint32)
        return np.stack(self._rows)[:, _I[name]]

    def _step_time_pcts(self) -> Optional[dict]:
        if self._times_us:
            return _pcts(np.asarray(self._times_us, dtype=np.float64))
        if self._chunk_times:
            # Device rings only know per-chunk averages: weight each average
            # by its step count (no count-many materialization).
            return _pcts_weighted(self._chunk_times)
        return None

    def summary(self, table_size: int, batch_size: int) -> dict:
        """The telemetry digest surfaced in `SearchResult.detail["telemetry"]`,
        bench rows, and `/metrics` (keys pinned by obs/schema.py)."""
        active = self._col("active")
        fills = self._col("table_claims").astype(np.float64) / max(table_size, 1)
        out = {
            "steps": int(self.steps),
            "captured_steps": len(self._rows),
            "dropped_steps": int(self.dropped_steps),
            "generated_total": int(self.generated_total),
            "claimed_total": int(self.claimed_total),
            "active_lanes": _pcts(active),
            "generated_per_step": _pcts(self._col("generated")),
            "claimed_per_step": _pcts(self._col("claimed")),
            "queue_len_max": int(self._col("queue_len").max()) if self._rows else 0,
            "fill": {
                "last": round(float(fills[-1]), 4) if self._rows else 0.0,
                "p95": round(float(np.percentile(fills, 95)), 4) if self._rows else 0.0,
                "max": round(float(fills.max()), 4) if self._rows else 0.0,
            },
            "lane_util": (
                round(float(active.mean()) / max(batch_size, 1), 4)
                if self._rows
                else 0.0
            ),
        }
        times = self._step_time_pcts()
        if times is not None:
            out["step_us"] = times
        suspects = self._col("suspects")
        if suspects.size and suspects.any():
            out["suspects_max"] = int(suspects.max())
        if self.per_shard_claimed is not None:
            mean = float(self.per_shard_claimed.mean())
            out["shard_imbalance"] = (
                round(float(self.per_shard_claimed.max()) / mean, 4)
                if mean > 0
                else 1.0
            )
        return out


def build_detail(
    store_stats: Optional[dict], telemetry: Optional[dict]
) -> Optional[dict]:
    """The shared `SearchResult.detail` assembly (obs/schema.py vocabulary):
    tier counters at the top level, the telemetry digest under
    "telemetry"; None when there is nothing to report (preserves the
    pre-obs `detail=None` shape for plain device-store runs)."""
    d = dict(store_stats or {})
    if telemetry is not None:
        d["telemetry"] = telemetry
    return d or None
