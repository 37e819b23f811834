"""The ONE registry of engine-knob string literals.

Before this module every spine spelled its own copy of the knob universes:
`FrontierSearch.INSERT_VARIANTS` named four insert designs, ResidentSearch
re-listed them inside an error message, the check-service scheduler re-typed
the store kinds, and `tensor/costmodel.py` kept a parallel variant alphabet —
the exact triple-implementation drift ROADMAP item 3's step-core refactor
will remove.  Until that refactor lands, this module is the drift *bound*:
every validation site imports its universe from here, and the srlint pass
(`stateright_tpu/analysis/`) flags any knob literal that is compared against
a variable without being a member of the registry — a typo'd
`store == "teired"` fails lint, not a benchmark three rounds later.

Deliberately pure Python (no jax import): the cost model, the analysis CLI,
and host-only tooling all read it without touching a backend.
"""

from __future__ import annotations

#: Visited-set insert designs accepted by FrontierSearch/ResidentSearch/
#: ShardedSearch (`insert_variant=`). "pallas" is the partitioned-VMEM
#: route-then-probe kernel (tensor/pallas_hashtable.py — SURVEY §7's
#: prescribed "open-addressing table in HBM updated by a Pallas kernel");
#: on the CPU backend it runs under Pallas interpret mode with exact
#: set/is_new parity to the XLA designs. The name → insert-fn dispatch
#: lives in ONE module, tensor/inserts.py, which all three engines import
#: (check_registry() pins the two against each other).
INSERT_VARIANTS = ("sort", "phased", "capped", "capped-phased", "pallas")

#: The subset of INSERT_VARIANTS built on the phased (claim-then-probe)
#: insert — these require the split table layout (hashtable's phased impl
#: has no kv lowering). Derived, not restated: srlint SR005 flags literal
#: copies of this subset exactly like full-universe restatements.
PHASED_VARIANTS = tuple(v for v in INSERT_VARIANTS if v.endswith("phased"))

#: Hash-table layouts (`table_layout=`): split lo/hi arrays vs interleaved
#: 64-slot kv buckets (hashtable._insert_impl_kv).
TABLE_LAYOUTS = ("split", "kv")

#: State-store kinds (`store=`): device-only hot set vs the two-tier
#: device + host-spill store (stateright_tpu/store/).
STORE_KINDS = ("device", "tiered")

#: Queue-append variants (`append=`): whole-array row scatter vs
#: compact-then-dynamic_update_slice (frontier.resolve_append).
APPEND_KINDS = ("scatter", "dus")

#: Engine spines (supervisor/adapter `engine=` selectors, chaos-plane
#: `engine=` context). "simulation" is the fourth checker mode
#: (tensor/simulation.py) — a first-class spine for faults/obs/bench
#: purposes, though the supervisor's degrade ladder drives the three
#: exhaustive spines only.
ENGINES = ("frontier", "resident", "sharded", "simulation")

#: Checker modes accepted by `CheckerBuilder.spawn_tpu(mode=)`: the batched
#: frontier search (the default) vs the device random-simulation engine
#: (tensor/simulation.py — the reference's fourth checker mode, SURVEY L2).
CHECKER_MODES = ("search", "simulation")

#: Device-simulation dedup designs (`dedup=` on DeviceSimulation /
#: spawn_simulation(device=True)): "trace" keeps an exact per-walk visited
#: table per lane (host SimulationChecker parity — no global dedup, so
#: unique_state_count == state_count), "shared" keeps a small per-walk depth
#: ring for cycle detection plus ONE global visited table shared by every
#: walk (the tensor/inserts.py dispatch table — capped/pallas variants,
#: job-salted fingerprints) so unique_state_count is real coverage and
#: stale walks can be restarted.
SIM_DEDUP_KINDS = ("trace", "shared")

#: Cost-model variant alphabet (tensor/costmodel.py) — the (table_layout,
#: insert_variant) product collapsed to the designs the roofline model
#: distinguishes. Kept here so the mapping below is checkable by lint/tests.
COST_VARIANTS = ("split", "kv", "phased", "capped", "capped-kv", "pallas")

#: Corpus warm-start match kinds (store/warm.py — the ONE warm-start seam,
#: ROADMAP item 4): "exact" replays a complete entry published under this
#: run's own content key, "near" replays a complete entry from the same
#: definition-hash family (different table packing; membership and results
#: are packing-invariant), "partial" resumes an incomplete entry's frontier
#: snapshot and continues the search naturally. "delta" is the Spec-CI
#: rung (store/specdelta.py): the DEFINITION changed, but the factored
#: component digests prove the edit salvageable — a properties-only edit
#: replays the visited set with verdicts re-evaluated, a boundary edit
#: replays or continues from a re-derived frontier; expand/init edits
#: refuse (counted, cold, never wrong). Every engine's warm path and the
#: `job.warm_start` event `kind` field draw from this tuple;
#: check_registry() pins the per-engine aliases against it.
WARM_KINDS = ("exact", "near", "partial", "delta")

#: Blob-store backends (`faults/blobstore.py`'s `backend_of` scheme
#: dispatch, the `--backend` smoke selector, the bench per-backend legs):
#: "file" is the local filesystem (plain path / ``file://``), "blob" the
#: in-house HTTP emulator (``blob://host:port``), "s3" and "gs" the
#: managed providers (``s3://bucket``/``gs://bucket`` — dialect
#: emulators in `faults/blobdialect.py` serve them hermetically). First
#: member is the non-wire default; `backend_of` dispatches on the rest,
#: in order, as URI schemes.
BLOB_BACKENDS = ("file", "blob", "s3", "gs")


def check_registry() -> list:
    """Cross-module drift probe used by `python -m stateright_tpu.analysis`:
    import every module that re-states a knob universe and report any
    disagreement with this registry (empty list = no drift). Imports are
    local so host-only callers (cost model, lint fixtures) never pay for
    jax."""
    problems: list[str] = []

    try:
        from .tensor import costmodel
    except ModuleNotFoundError as e:
        # The costmodel module is jax-free but lives under the jax-importing
        # tensor package; on a jax-free image the cross-module probe simply
        # cannot run (srlint SR005 still covers literal drift there).
        if e.name and e.name.split(".")[0] in ("jax", "jaxlib"):
            return problems
        raise

    # costmodel re-exports the registry tuple by reference; a set-equality
    # check would be vacuous, so probe that the alias is still an alias —
    # re-typing the tuple in costmodel.py is exactly the drift this guards.
    if costmodel.INSERT_VARIANTS is not COST_VARIANTS:
        problems.append(
            "costmodel.INSERT_VARIANTS is a restated copy, not the "
            "knobs.COST_VARIANTS alias: "
            f"{sorted(costmodel.INSERT_VARIANTS)} vs {sorted(COST_VARIANTS)}"
        )
    for (layout, variant), cost in costmodel.ENGINE_VARIANTS.items():
        if layout not in TABLE_LAYOUTS:
            problems.append(
                f"costmodel.ENGINE_VARIANTS layout {layout!r} not in "
                "knobs.TABLE_LAYOUTS"
            )
        if variant not in INSERT_VARIANTS:
            problems.append(
                f"costmodel.ENGINE_VARIANTS insert variant {variant!r} not "
                "in knobs.INSERT_VARIANTS"
            )
        if cost not in COST_VARIANTS:
            problems.append(
                f"costmodel.ENGINE_VARIANTS cost variant {cost!r} not in "
                "knobs.COST_VARIANTS"
            )

    # The warm-start seam (store/warm.py) is jax-free like this module:
    # probe its alias before the jax-importing engine block so even a
    # jax-free image catches a restated WARM_KINDS copy there.
    from .store import warm

    if warm.WARM_KINDS is not WARM_KINDS:
        problems.append(
            "store.warm.WARM_KINDS is a restated copy, not the "
            "knobs.WARM_KINDS alias"
        )

    # The URI dispatcher (faults/blobstore.py — jax-free like this module)
    # must dispatch over THE backend tuple: `backend_of` iterates
    # BLOB_BACKENDS[1:] as URI schemes, so a restated copy there would let
    # a new scheme land in one place and silently not the other.
    from .faults import blobstore

    if blobstore.BLOB_BACKENDS is not BLOB_BACKENDS:
        problems.append(
            "faults.blobstore.BLOB_BACKENDS is a restated copy, not the "
            "knobs.BLOB_BACKENDS alias"
        )
    for backend in BLOB_BACKENDS:
        probe = {
            "file": "/tmp/x", "blob": "blob://h:1/x",
            "s3": "s3://b/x", "gs": "gs://b/x",
        }[backend]
        if blobstore.backend_of(probe) != backend:
            problems.append(
                f"blobstore.backend_of does not round-trip backend "
                f"{backend!r} (probe {probe!r})"
            )

    try:
        from .parallel.sharded import ShardedSearch
        from .service.scheduler import ServiceEngine
        from .tensor import inserts
        from .tensor.frontier import FrontierSearch
        from .tensor.resident import ResidentSearch
        from .tensor.simulation import DeviceSimulation
    except ModuleNotFoundError as e:
        # jax-free images run the lint half only (`--skip-audit`); the
        # engine cross-check needs the jax-importing spine and is the one
        # probe that cannot run there.
        if e.name and e.name.split(".")[0] in ("jax", "jaxlib"):
            return problems
        raise

    # The dispatch table (tensor/inserts.py) must cover exactly this
    # registry's variant names — a variant registered here without a
    # dispatch entry (or vice versa) is the r10 drift class this module
    # exists to bound.
    if set(inserts.INSERT_TABLE) != set(INSERT_VARIANTS):
        problems.append(
            "inserts.INSERT_TABLE keys != knobs.INSERT_VARIANTS: "
            f"{sorted(inserts.INSERT_TABLE)} vs {sorted(INSERT_VARIANTS)}"
        )
    if not set(inserts.KV_INSERT_TABLE) <= set(INSERT_VARIANTS):
        problems.append(
            "inserts.KV_INSERT_TABLE names a variant outside "
            f"knobs.INSERT_VARIANTS: {sorted(inserts.KV_INSERT_TABLE)}"
        )
    # The engines must all dispatch through THE table, not a restated copy
    # (same alias-identity probe as the costmodel tuple above).
    if FrontierSearch.INSERT_VARIANTS is not inserts.INSERT_TABLE:
        problems.append(
            "FrontierSearch.INSERT_VARIANTS is a restated copy, not the "
            "inserts.INSERT_TABLE alias"
        )
    if ServiceEngine.INSERT_VARIANTS is not inserts.INSERT_TABLE:
        problems.append(
            "ServiceEngine.INSERT_VARIANTS is a restated copy, not the "
            "inserts.INSERT_TABLE alias"
        )
    # The fourth engine's dedup universe must be THE registry tuple (alias
    # identity, same probe as the costmodel alias above), and its shared
    # visited table must resolve through the one insert dispatch table.
    if DeviceSimulation.DEDUP_KINDS is not SIM_DEDUP_KINDS:
        problems.append(
            "DeviceSimulation.DEDUP_KINDS is a restated copy, not the "
            "knobs.SIM_DEDUP_KINDS alias"
        )
    # Corpus warm-start: every engine (and the service scheduler) must
    # alias the one WARM_KINDS tuple AND the one preload seam — a private
    # per-engine warm path is exactly the restatement ROADMAP item 4(c)
    # removed (the resident/sharded/simulation warm-start gap).
    for cls in (
        FrontierSearch, ResidentSearch, ShardedSearch, DeviceSimulation,
        ServiceEngine,
    ):
        if getattr(cls, "WARM_KINDS", None) is not WARM_KINDS:
            problems.append(
                f"{cls.__name__}.WARM_KINDS is a restated copy, not the "
                "knobs.WARM_KINDS alias"
            )
        if getattr(cls, "WARM_SEAM", None) is not warm:
            problems.append(
                f"{cls.__name__}.WARM_SEAM is not the store.warm module "
                "(the one warm-start/preload seam)"
            )
    return problems
