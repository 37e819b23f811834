"""Calibration observatory: live measured-vs-predicted cost attribution.

The costmodel (tensor/costmodel.py) ranks designs from COMMITTED
predictions; the r9 telemetry ring measures what the engines actually do.
Before this module the two never met in code — recalibrating a roofline
term was a by-hand exercise over raw sweep JSON. This module closes the
loop host-side:

- `Comparator` joins each engine's already-drained step telemetry (the
  per-chunk ``(steps, window_us)`` pairs every engine computes at its
  existing sync boundaries — NO new device wiring) against the
  costmodel's per-step prediction for that exact config, producing
  ``detail["calib"]`` (schema.CALIB_DETAIL_KEYS), the ``"calib"``
  REGISTRY source, and a seeded-band drift detector that journals a
  ``calib.drift`` event when measured/predicted leaves [0.7, 1.4] for K
  consecutive chunks.
- Observations are flushed as CRC'd records through the ckptio record
  seam into a shared root (``SR_TPU_CALIB_DIR`` or an explicit root —
  `file://` or `blob://`, exactly like every other durable surface), so
  every fleet replica contributes rows to one corpus.
- `fit_theta` least-squares-fits the costmodel coefficient vector from
  that corpus. The fit is exact-by-construction: every predicted step
  time is LINEAR in theta = (1/gbps_gather, 1/gbps_sort, 1/gbps_scatter,
  1/gbps_stream, ns_expand_elem, ns_other_lane, ms_dispatch,
  1/pcie_gbps), so each observation stores its 8 basis features (the
  cost function evaluated at unit-theta DeviceSpecs) and the fitter is a
  steps-weighted lstsq with a small ridge toward the stock spec for
  directions the corpus never excites.

The observatory OBSERVES — it never steers. Search results are
bit-identical with the comparator on or off (``SR_TPU_CALIB=0``), and a
fitted overlay (`costmodel.load_calibration`) is a new DeviceSpec, never
a mutation of the committed V5E/CPU1 anchors.
"""

from __future__ import annotations

import json
import logging
import math
import os
import time
from dataclasses import dataclass, replace
from typing import Optional

from ..tensor import costmodel
from ..tensor.costmodel import DeviceSpec, StepCost
from .events import NULL_EVENTS, as_events
from .ring import _pcts_weighted

#: Record magic for the shared CRC'd record footer (ckptio.RECORD_FOOTER).
CALIB_MAGIC = b"SRTPCAL1"

#: Kill switch: SR_TPU_CALIB=0 disables every comparator (the bench A/B
#: knob); default is on — the comparator is pure host arithmetic at chunk
#: granularity.
ENV_ENABLE = "SR_TPU_CALIB"
#: Record root for durable observations (file:// dir or blob:// URI).
#: Unset = observations stay in-process (detail/metrics only).
ENV_DIR = "SR_TPU_CALIB_DIR"
#: Override the device-kind guess ("cpu-1core" | "tpu-v5e").
ENV_DEVICE = "SR_TPU_CALIB_DEVICE"
#: Override the chunk size (steps per measured-vs-predicted comparison;
#: default 32). Small values let short smoke runs close several chunks.
ENV_CHUNK = "SR_TPU_CALIB_CHUNK"

#: Seeded drift band on measured/predicted, and the consecutive-chunk
#: count that arms an episode (ISSUE 19 seed values).
DRIFT_BAND = (0.7, 1.4)
DRIFT_CONSECUTIVE = 3

#: theta component names, in fit order. Each maps to one DeviceSpec rate
#: field; "inv" components enter predictions as 1/field (bandwidths),
#: "lin" components enter directly (per-element ns, per-dispatch ms).
THETA_FIELDS = (
    ("gather", "gbps_gather", "inv"),
    ("sort", "gbps_sort", "inv"),
    ("scatter", "gbps_scatter", "inv"),
    ("stream", "gbps_stream", "inv"),
    ("expand", "ns_expand_elem", "lin"),
    ("other", "ns_other_lane", "lin"),
    ("dispatch", "ms_dispatch", "lin"),
    ("pcie", "pcie_gbps", "inv"),
)
THETA_NAMES = tuple(n for n, _f, _k in THETA_FIELDS)

_INF_GBPS = 1e18  # a bandwidth so high its 1/gbps theta component is ~0


def calib_enabled() -> bool:
    """Off with SR_TPU_CALIB=0, and off — with the reason logged — on a
    device the cost model has no peaks for."""
    if os.environ.get(ENV_ENABLE, "1") == "0":
        return False
    try:
        default_device_kind()
    except ValueError as e:
        logging.getLogger(__name__).warning("calibration off: %s", e)
        return False
    return True


def default_device_kind() -> str:
    """Spec name for prediction: env override, else the committed spec of
    `jax.devices()[0].device_kind` (ValueError for a kind the cost model's
    table does not list — never another device's peaks)."""
    kind = os.environ.get(ENV_DEVICE)
    if kind:
        return kind
    import jax  # resident in every engine process already; stay lazy

    return costmodel.spec_for_device_kind(jax.devices()[0].device_kind).name


def theta_of(device: DeviceSpec) -> list:
    """The 8-vector the cost functions are linear in, for `device`."""
    out = []
    for _n, field, kind in THETA_FIELDS:
        v = float(getattr(device, field))
        out.append(1.0 / v if kind == "inv" else v)
    return out


def device_from_theta(base: DeviceSpec, theta) -> DeviceSpec:
    """A NEW DeviceSpec with `base`'s name/peak and `theta`'s rates — the
    overlay constructor; the committed anchors are never mutated."""
    kw = {}
    for (_n, field, kind), t in zip(THETA_FIELDS, theta):
        t = max(float(t), 1e-12)
        kw[field] = (1.0 / t) if kind == "inv" else t
    return replace(base, **kw)


def _basis_device(index: Optional[int]) -> DeviceSpec:
    """A DeviceSpec whose theta is the `index`-th unit vector (None = the
    all-zeros spec, isolating any constant term in the predictor)."""
    kw = dict(
        name="basis",
        hbm_gbps=_INF_GBPS,
        gbps_gather=_INF_GBPS,
        gbps_sort=_INF_GBPS,
        gbps_scatter=_INF_GBPS,
        gbps_stream=_INF_GBPS,
        ns_expand_elem=0.0,
        ns_other_lane=0.0,
        ms_dispatch=0.0,
        pcie_gbps=_INF_GBPS,
    )
    if index is not None:
        _n, field, kind = THETA_FIELDS[index]
        kw[field] = 1.0 if kind == "inv" else 1.0
        if kind == "lin":
            kw[field] = 1.0
    return DeviceSpec(**kw)


@dataclass(frozen=True)
class CalibConfig:
    """One engine run's prediction config — everything the cost functions
    need beyond the DeviceSpec. `batch` is the step batch (traces for the
    simulation engine, per-shard batch for the sharded engine)."""

    engine: str  # "frontier" | "resident" | "sharded" | "simulation" | "service"
    variant: str  # costmodel variant name (ENGINE_VARIANTS value)
    lanes: int
    max_actions: int
    batch: int
    table_log2: int
    sim: bool = False  # price with sim_step_cost instead of step_cost
    dedup: str = "trace"  # simulation engine only
    cycle_log2: int = 9
    ring: int = 64
    spill: bool = False  # tiered store active (summary-probe term)

    def predict(
        self, device: DeviceSpec, new_frac: float = 0.5
    ) -> StepCost:
        if self.sim:
            return costmodel.sim_step_cost(
                self.lanes,
                self.max_actions,
                max(self.batch, 1),
                dedup=self.dedup,
                cycle_log2=self.cycle_log2,
                ring=self.ring,
                table_log2=self.table_log2,
                variant=self.variant,
                device=device,
            )
        return costmodel.step_cost(
            self.lanes,
            self.max_actions,
            max(self.batch, 1),
            self.table_log2,
            variant=self.variant,
            new_frac=new_frac,
            device=device,
            spill={"summary_hashes": 4} if self.spill else None,
        )

    def features(self, new_frac: float = 0.5) -> tuple:
        """(c0, [f_0..f_7]) with predicted_ms == c0 + f . theta for ANY
        theta — the linearity the fitter rests on (pinned by
        tests/test_calib.py against direct evaluation)."""
        c0 = self.predict(_basis_device(None), new_frac).total_ms
        feats = [
            self.predict(_basis_device(i), new_frac).total_ms - c0
            for i in range(len(THETA_FIELDS))
        ]
        return c0, feats


def _quantize_frac(new_frac: float) -> float:
    """Bucket new_frac to 1/32 steps so feature vectors cache."""
    return max(1.0 / 32.0, min(1.0, round(new_frac * 32.0) / 32.0))


def _safe_key(s: str) -> str:
    return "".join(c if c.isalnum() or c in "-_." else "_" for c in s)


class Comparator:
    """Host-side measured-vs-predicted join for ONE engine instance.

    Engines call `observe(steps_total, window_us, generated_total)` at
    their existing drain boundaries (per host step for frontier/service,
    per device-ring drain for resident/sharded, per dispatch round for
    simulation); the comparator accumulates until `chunk_steps` steps
    close a chunk, then compares the chunk's measured ms/step against the
    costmodel prediction for this exact config. Everything here is plain
    Python arithmetic on numbers the engine already computed — nothing
    touches the device, and nothing feeds back into the search.
    """

    def __init__(
        self,
        config: CalibConfig,
        *,
        device: Optional[DeviceSpec] = None,
        band: tuple = DRIFT_BAND,
        k_consecutive: int = DRIFT_CONSECUTIVE,
        chunk_steps: Optional[int] = None,
        events=None,
        record_root: Optional[str] = None,
        max_rows: int = 512,
    ):
        self.config = config
        self.device = device if device is not None else active_device()
        self.band = (float(band[0]), float(band[1]))
        self.k_consecutive = max(int(k_consecutive), 1)
        if chunk_steps is None:
            chunk_steps = int(os.environ.get(ENV_CHUNK, "32") or 32)
        self.chunk_steps = max(int(chunk_steps), 1)
        self.events = as_events(events) if events is not None else NULL_EVENTS
        self.record_root = record_root
        self.max_rows = max_rows
        self._theta = theta_of(self.device)
        # (c0, feats, {op: ms}) per quantized new_frac bucket.
        self._cache: dict = {}
        # watermarks into the engine's cumulative telemetry counters
        self._seen_steps = 0
        self._seen_gen = 0
        self._pending_steps = 0
        self._pending_us = 0.0
        self._pending_gen = 0
        self._have_gen = False
        # chunk digest ((steps, ms_per_step) and (steps, ratio) pairs)
        self._chunk_ms: list = []
        self._chunk_ratio: list = []
        # drift state
        self._consecutive = 0
        self._episode = False
        # counters (CALIB_COUNTER_KEYS)
        self.chunks = 0
        self.out_of_band = 0
        self.drift_events = 0
        self.records_flushed = 0
        self.record_errors = 0
        self.last_ratio = 0.0
        self.last_predicted_ms = 0.0
        self.last_measured_ms = 0.0
        self.last_new_frac = 0.5
        self.last_top_term = ""
        # durable observation rows (flushed through ckptio.write_record)
        self._rows: list = []
        self._rows_unflushed = 0
        self._last_traces: list = []

    # -- geometry -----------------------------------------------------------

    def configure(self, lanes: int, max_actions: int) -> None:
        """Re-point the prediction at a new (lanes, max_actions) geometry
        (the service engine's groups change between jobs). Invalidates
        the feature cache; watermarks and counters carry over."""
        if (
            lanes == self.config.lanes
            and max_actions == self.config.max_actions
        ):
            return
        self.config = replace(
            self.config, lanes=int(lanes), max_actions=int(max_actions)
        )
        self._cache.clear()

    # -- the join -----------------------------------------------------------

    def _bucket(self, new_frac: float) -> tuple:
        q = _quantize_frac(new_frac)
        hit = self._cache.get(q)
        if hit is None:
            c0, feats = self.config.features(q)
            sc = self.config.predict(self.device, q)
            terms = {op.name: op.ms for op in sc.ops}
            hit = (c0, feats, terms)
            self._cache[q] = hit
        return (q,) + hit

    def observe(
        self,
        steps_total: int,
        window_us: float,
        generated_total: Optional[int] = None,
        traces=None,
    ) -> None:
        """Feed one already-synced telemetry drain: the engine's
        cumulative step count, the wall microseconds the new steps took,
        and (optionally) the cumulative generated-state count that prices
        the capped variants' `new_frac`. `traces` is an optional list of
        job trace ids active in the window, carried onto any drift event
        so the timeline CLI can answer "which job"."""
        steps_total = int(steps_total)
        if steps_total < self._seen_steps:  # engine restart/rebuild
            self._seen_steps = 0
            self._seen_gen = 0
        d_steps = steps_total - self._seen_steps
        self._seen_steps = steps_total
        if d_steps <= 0 or window_us is None or window_us <= 0:
            return
        if generated_total is not None:
            generated_total = int(generated_total)
            if generated_total >= self._seen_gen:
                self._pending_gen += generated_total - self._seen_gen
                self._have_gen = True
            self._seen_gen = generated_total
        if traces:
            self._last_traces = list(traces)[:8]
        self._pending_steps += d_steps
        self._pending_us += float(window_us)
        while self._pending_steps >= self.chunk_steps:
            self._close_chunk()

    def _close_chunk(self) -> None:
        steps = self._pending_steps
        ms_per_step = (self._pending_us / 1000.0) / steps
        flat = steps * self.config.batch * self.config.max_actions
        if self._have_gen and flat > 0:
            new_frac = self._pending_gen / flat
        else:
            new_frac = 0.5
        self._pending_steps = 0
        self._pending_us = 0.0
        self._pending_gen = 0
        self._have_gen = False

        q, c0, feats, terms = self._bucket(new_frac)
        predicted = c0 + sum(f * t for f, t in zip(feats, self._theta))
        ratio = ms_per_step / max(predicted, 1e-9)
        top = max(terms.items(), key=lambda kv: kv[1])[0] if terms else ""

        self.chunks += 1
        self.last_ratio = ratio
        self.last_predicted_ms = predicted
        self.last_measured_ms = ms_per_step
        self.last_new_frac = q
        self.last_top_term = top
        if len(self._chunk_ms) < 4096:
            self._chunk_ms.append((steps, ms_per_step))
            self._chunk_ratio.append((steps, ratio))
        if len(self._rows) < self.max_rows:
            self._rows.append({
                "ms": round(ms_per_step, 6),
                "steps": steps,
                "new_frac": q,
                "c0": round(c0, 9),
                "f": [round(f, 9) for f in feats],
                "ratio": round(ratio, 4),
            })
            self._rows_unflushed += 1

        lo, hi = self.band
        if ratio < lo or ratio > hi:
            self.out_of_band += 1
            self._consecutive += 1
            if self._consecutive >= self.k_consecutive and not self._episode:
                self._episode = True
                self.drift_events += 1
                self.events.emit(
                    "calib.drift",
                    engine=self.config.engine,
                    term=top,
                    ratio=round(ratio, 3),
                    predicted_ms=round(predicted, 4),
                    measured_ms=round(ms_per_step, 4),
                    variant=self.config.variant,
                    device=self.device.name,
                    jobs=self._last_traces or None,
                )
        else:
            self._consecutive = 0
            self._episode = False

    def finish(self) -> None:
        """Close any partial chunk (run end IS a sync boundary): short
        runs — the exhaustive goldens finish in a dozen steps — still get
        a populated `detail["calib"]` instead of an empty comparator."""
        if self._pending_steps > 0:
            self._close_chunk()

    # -- surfaces -----------------------------------------------------------

    def drift_ratio(self) -> Optional[float]:
        """Latest chunk's measured/predicted, or None before the first
        chunk (the reporter's `drift=` field)."""
        return self.last_ratio if self.chunks else None

    def detail(self) -> dict:
        """The `detail["calib"]` sub-dict (schema.CALIB_DETAIL_KEYS)."""
        _q, c0, feats, terms = self._bucket(self.last_new_frac)
        ms = _pcts_weighted(self._chunk_ms)
        ratio = _pcts_weighted(self._chunk_ratio)
        return {
            "engine": self.config.engine,
            "variant": self.config.variant,
            "device": self.device.name,
            "predicted_ms": round(self.last_predicted_ms, 4),
            "measured_p50_ms": round(ms["p50"], 4),
            "measured_p95_ms": round(ms["p95"], 4),
            "drift_ratio": round(ratio["p50"], 4),
            "new_frac": self.last_new_frac,
            "chunks": self.chunks,
            "out_of_band": self.out_of_band,
            "drift_events": self.drift_events,
            "terms": {k: round(v, 4) for k, v in terms.items()},
            "top_term": self.last_top_term,
        }

    def metrics(self) -> dict:
        """The `"calib"` REGISTRY source (schema.CALIB_COUNTER_KEYS)."""
        return {
            "chunks": self.chunks,
            "out_of_band": self.out_of_band,
            "drift_events": self.drift_events,
            "drift_active": int(self._episode),
            "last_ratio": round(self.last_ratio, 4),
            "last_predicted_ms": round(self.last_predicted_ms, 4),
            "last_measured_ms": round(self.last_measured_ms, 4),
            "records_flushed": self.records_flushed,
            "record_errors": self.record_errors,
        }

    # -- durable records ----------------------------------------------------

    def record_key(self) -> str:
        c = self.config
        return _safe_key(
            f"{self.device.name}-{c.engine}-{c.variant}"
            f"-l{c.lanes}a{c.max_actions}b{c.batch}t{c.table_log2}"
            + ("-sim-" + c.dedup if c.sim else "")
            + ("-spill" if c.spill else "")
        )

    def flush_records(self, root: Optional[str] = None) -> int:
        """Merge this comparator's observation rows into the durable
        record for its (device x engine x variant x geometry) key under
        `root` (default ``SR_TPU_CALIB_DIR`` / the constructor root).
        Best-effort: an unreachable store counts `record_errors` and the
        run proceeds — calibration must never fail a check."""
        root = root or os.environ.get(ENV_DIR) or self.record_root
        if not root or not self._rows_unflushed:
            return 0
        try:
            n = write_observations(
                root,
                self.record_key(),
                self._rows,
                meta=self.config,
                device=self.device,
                max_rows=self.max_rows,
            )
        except (OSError, ValueError):
            self.record_errors += 1
            return 0
        self.records_flushed += 1
        self._rows_unflushed = 0
        return n


# -- durable record I/O (through the ckptio CRC seam) -----------------------


def _calib_dir(root: str) -> str:
    from ..faults.blobstore import normalize_root

    return os.path.join(normalize_root(root), "calib")


def record_path(root: str, key: str) -> str:
    return os.path.join(_calib_dir(root), f"calib-{_safe_key(key)}.json")


def write_observations(
    root: str,
    key: str,
    rows: list,
    *,
    meta: Optional[CalibConfig] = None,
    device: Optional[DeviceSpec] = None,
    max_rows: int = 512,
) -> int:
    """Merge `rows` into the record at (root, key) — read-modify-write
    through `ckptio.write_record`, newest rows kept, bounded at
    `max_rows`. Returns the row count written."""
    from ..faults.blobstore import is_blob_uri
    from ..faults.ckptio import read_record_latest, write_record

    path = record_path(root, key)
    if not is_blob_uri(path):
        os.makedirs(os.path.dirname(path), exist_ok=True)
    existing, _any = read_record_latest(path, CALIB_MAGIC)
    old_rows = []
    if existing is not None:
        try:
            old = json.loads(existing)
            if isinstance(old, dict):
                old_rows = list(old.get("rows") or [])
        except ValueError:
            old_rows = []
    merged = (old_rows + list(rows))[-max_rows:]
    rec = {
        "key": key,
        "ts": round(time.time(), 3),
        "rows": merged,
    }
    if meta is not None:
        rec["engine"] = meta.engine
        rec["variant"] = meta.variant
        rec["geometry"] = {
            "lanes": meta.lanes,
            "max_actions": meta.max_actions,
            "batch": meta.batch,
            "table_log2": meta.table_log2,
            "sim": meta.sim,
            "spill": meta.spill,
        }
    if device is not None:
        rec["device"] = device.name
    write_record(path, json.dumps(rec).encode(), CALIB_MAGIC)
    return len(merged)


def load_observations(root: str) -> list:
    """Every intact calibration record under `root` (local or blob://):
    [{"key", "device", "engine", "variant", "geometry", "rows"}...]."""
    from ..faults.blobstore import blob_backend
    from ..faults.ckptio import read_record_latest

    d = _calib_dir(root)
    out = []
    try:
        listing = blob_backend(d).list("calib-")
    except OSError:
        return out
    for st in listing:
        if st.name.endswith(".prev"):
            continue
        payload, _any = read_record_latest(
            os.path.join(d, st.name), CALIB_MAGIC
        )
        if payload is None:
            continue
        try:
            rec = json.loads(payload)
        except ValueError:
            continue
        if isinstance(rec, dict) and rec.get("rows"):
            out.append(rec)
    return out


# -- the fitter -------------------------------------------------------------


def fit_theta(
    records: list,
    base: DeviceSpec,
    *,
    ridge: float = 1e-2,
) -> tuple:
    """Steps-weighted least-squares fit of theta from accumulated
    observation records (the `load_observations` shape), ridged toward
    `base`'s theta so directions the corpus never excites (e.g. the pcie
    term with no spill runs) stay at the committed value instead of
    drifting to the min-norm garbage lstsq would pick.

    Returns (theta, report) where report carries per-row residual ratios
    under the stock and fitted vectors.
    """
    import numpy as np

    rows = [r for rec in records for r in rec.get("rows", [])]
    if not rows:
        raise ValueError("no calibration observations to fit")
    theta0 = np.asarray(theta_of(base), dtype=float)
    A = np.asarray([r["f"] for r in rows], dtype=float)
    c0 = np.asarray([r.get("c0", 0.0) for r in rows], dtype=float)
    b = np.asarray([r["ms"] for r in rows], dtype=float) - c0
    w = np.sqrt(np.asarray(
        [max(float(r.get("steps", 1)), 1.0) for r in rows]
    ))
    Aw = A * w[:, None]
    bw = b * w
    # Ridge toward base theta, scaled per column so the prior has the
    # same units as the data rows it competes with.
    col = np.abs(Aw).max(axis=0)
    lam = ridge * np.where(col > 0, col, 1.0)
    Ar = np.vstack([Aw, np.diag(lam)])
    br = np.concatenate([bw, lam * theta0])
    sol, *_ = np.linalg.lstsq(Ar, br, rcond=None)
    theta = np.maximum(sol, theta0 * 1e-3)  # keep every rate physical
    theta = np.minimum(theta, np.maximum(theta0 * 1e3, 1e-12))

    def _ratios(t):
        pred = c0 + A @ t
        return np.abs(b + c0) / np.maximum(pred, 1e-9)

    r_stock = _ratios(theta0)
    r_fit = _ratios(theta)
    report = {
        "rows": len(rows),
        "median_abs_drift_stock": float(np.median(np.abs(r_stock - 1.0))),
        "median_abs_drift_fitted": float(np.median(np.abs(r_fit - 1.0))),
        "theta_stock": [float(t) for t in theta0],
        "theta_fitted": [float(t) for t in theta],
    }
    return [float(t) for t in theta], report


def overlay_dict(base: DeviceSpec, theta, report: Optional[dict] = None) -> dict:
    """The loadable overlay payload `costmodel.load_calibration` reads."""
    spec = device_from_theta(base, theta)
    rates = {
        field: getattr(spec, field) for _n, field, _k in THETA_FIELDS
    }
    out = {"base": base.name, "theta": list(theta), "rates": rates}
    if report:
        out["fit"] = {
            k: report[k]
            for k in ("rows", "median_abs_drift_stock",
                      "median_abs_drift_fitted")
            if k in report
        }
    return out


def active_device(kind: Optional[str] = None) -> DeviceSpec:
    """The DeviceSpec predictions should use right now: the loaded
    calibration overlay when one is active for this device kind, else
    the stock committed spec."""
    kind = kind or default_device_kind()
    stock = costmodel.stock_device(kind)
    cal = costmodel.load_calibration()
    if cal is not None and cal.name == stock.name:
        return cal
    return stock


def holdout_eval(records: list, base: DeviceSpec, *, ridge: float = 1e-2) -> dict:
    """Leave-one-key-out evaluation: for each record key, fit on every
    OTHER key's rows and score median |ratio-1| on the held-out key under
    stock vs fitted theta — the acceptance-criterion measurement
    (`tpu_tune --calibrate` prints it)."""
    import numpy as np

    keys = [rec.get("key", str(i)) for i, rec in enumerate(records)]
    out = {}
    for i, key in enumerate(keys):
        train = [rec for j, rec in enumerate(records) if j != i]
        if not train:
            continue
        try:
            theta, _rep = fit_theta(train, base, ridge=ridge)
        except ValueError:
            continue
        rows = records[i].get("rows", [])
        if not rows:
            continue
        A = np.asarray([r["f"] for r in rows], dtype=float)
        c0 = np.asarray([r.get("c0", 0.0) for r in rows], dtype=float)
        ms = np.asarray([r["ms"] for r in rows], dtype=float)
        t0 = np.asarray(theta_of(base))
        t1 = np.asarray(theta)
        r0 = ms / np.maximum(c0 + A @ t0, 1e-9)
        r1 = ms / np.maximum(c0 + A @ t1, 1e-9)
        out[key] = {
            "stock": float(np.median(np.abs(r0 - 1.0))),
            "fitted": float(np.median(np.abs(r1 - 1.0))),
        }
    return out
