#!/usr/bin/env python
"""Device-simulation smoke: host walker -> device cold -> device shared-dedup,
end to end on one model (2pc-3).

CI-shaped: exercises the whole fourth-checker-mode plane (ISSUE 14,
stateright_tpu/tensor/simulation.py) in one command —

1. HOST: the thread-pool `SimulationChecker` walks the 2pc-3 anchor to a
   state budget (the reference's per-thread trace loop).
2. DEVICE COLD: the continuous-batched device engine with per-walk dedup
   (`dedup="trace"` — host-parity accounting, unique == states) through
   the first-class wiring (`spawn_tpu(mode="simulation")`).
3. DEVICE SHARED: the shared visited table (`dedup="shared"`) — real
   unique coverage bounded by the exhaustive golden, nonzero dedup hits.

Asserts: identical property verdicts on all three sides (abort agreement
found, safety never violated), nonzero lane restarts (continuous batching
actually engaged), and a replayable counterexample path (the discovery
re-executes through the model to a valid `Path`).

Exit code 0 iff every phase agreed.

    JAX_PLATFORMS=cpu python scripts/sim_smoke.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    from stateright_tpu.core.discovery import HasDiscoveries
    from stateright_tpu.examples.two_phase_commit import TwoPhaseSys
    from stateright_tpu.tensor.models import TensorTwoPhaseSys
    from stateright_tpu.tensor.simulation import DeviceSimulation

    target = 30_000
    failures = []

    # -- 1. host walker --------------------------------------------------------
    t0 = time.monotonic()
    host = (
        TwoPhaseSys(3)
        .checker()
        .target_state_count(target)
        .spawn_simulation(seed=0)
        .join()
    )
    host_sec = time.monotonic() - t0
    host_found = set(host.discoveries())
    print(
        f"host: {host.state_count()} states in {host_sec:.2f}s, "
        f"found={sorted(host_found)}"
    )

    # -- 2. device cold (per-walk dedup, first-class wiring) -------------------
    t0 = time.monotonic()
    cold = (
        TensorTwoPhaseSys(3)
        .checker()
        .finish_when(HasDiscoveries.ALL)
        .target_state_count(target)
        .spawn_tpu(mode="simulation", traces=256, max_depth=64)
        .join()
    )
    cold_sec = time.monotonic() - t0
    cold_found = set(cold.discoveries())
    cold_tel = cold.telemetry_summary()
    print(
        f"device cold: {cold.state_count()} states in {cold_sec:.2f}s "
        f"(walks={cold_tel['walks']}, restarts={cold_tel['restarts']}, "
        f"lane_util={cold_tel['lane_util']}), found={sorted(cold_found)}"
    )
    if cold.unique_state_count() != cold.state_count():
        failures.append("device cold: unique != states under dedup='trace'")
    if cold_tel["restarts"] == 0:
        failures.append("device cold: continuous batching never restarted")

    # -- 3. device shared-dedup ------------------------------------------------
    sim = DeviceSimulation(
        TensorTwoPhaseSys(3), seed=0, traces=256, max_depth=64,
        dedup="shared", table_log2=16,
    )
    r = sim.run()
    while r.state_count < target:
        r = sim.run()
    tel = r.detail["telemetry"]
    shared_found = set(r.discoveries)
    print(
        f"device shared: {r.state_count} states, unique={r.unique_state_count} "
        f"(dedup_hit_rate={tel['dedup_hit_rate']}, walks={tel['walks']}), "
        f"found={sorted(shared_found)}"
    )
    if not 0 < r.unique_state_count <= 288:
        failures.append(
            f"device shared: unique {r.unique_state_count} outside the "
            "2pc-3 exhaustive golden bound (288)"
        )
    if tel["dedup_hit_rate"] <= 0:
        failures.append("device shared: dedup never hit")
    if tel["restarts"] == 0:
        failures.append("device shared: continuous batching never restarted")

    # -- verdict parity across all three sides ---------------------------------
    for found, side in (
        (host_found, "host"),
        (cold_found, "device-cold"),
        (shared_found, "device-shared"),
    ):
        if "abort agreement" not in found:
            failures.append(f"{side}: missed 'abort agreement'")
        if "consistent" in found:
            failures.append(f"{side}: safety 'consistent' falsely violated")
    if host_found != cold_found or host_found != shared_found:
        failures.append(
            f"verdict sets differ: host={sorted(host_found)} "
            f"cold={sorted(cold_found)} shared={sorted(shared_found)}"
        )

    # -- replayable counterexample path ----------------------------------------
    name = "abort agreement"
    if name in shared_found:
        path = sim.discovery_path(name)
        states = path.states()
        if len(states) != len(sim._discoveries[name]):
            failures.append(
                f"discovery path replay length {len(states)} != recorded "
                f"fingerprint chain {len(sim._discoveries[name])}"
            )
        else:
            print(
                f"replayed '{name}' counterexample: {len(states)} states, "
                f"ends at {states[-1]}"
            )

    if failures:
        print("\nSIM SMOKE FAILED:")
        for f in failures:
            print(f"  - {f}")
        return 1
    print("\nSIM SMOKE OK: host/device verdicts identical, restarts "
          "engaged, counterexample replays.")
    return 0


if __name__ == "__main__":
    sys.exit(main())
