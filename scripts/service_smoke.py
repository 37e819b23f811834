#!/usr/bin/env python
"""Check-service smoke: submit 8 mixed jobs, assert all complete, print
jobs/sec.

CI-shaped: exercises the whole serving path — admission, continuous
batching across model groups, shared-table salting, result/discovery
retrieval — in one command. Exit code 0 iff every job completed with its
expected golden counts.

    JAX_PLATFORMS=cpu python scripts/service_smoke.py [--tiered]
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

GOLD = {
    "2pc-3": (1_146, 288),
    "2pc-4": (8_258, 1_568),
    "inclock-4": (257, 257),
}


def main(argv) -> int:
    from stateright_tpu.service import CheckService
    from stateright_tpu.tensor.models import (
        TensorIncrementLock,
        TensorTwoPhaseSys,
    )

    tiered = "--tiered" in argv
    m3, m4, mi = (
        TensorTwoPhaseSys(3), TensorTwoPhaseSys(4), TensorIncrementLock(4)
    )
    jobs = [
        ("2pc-3", m3), ("2pc-3", m3), ("2pc-3", m3),
        ("2pc-4", m4), ("2pc-4", m4), ("2pc-4", m4),
        ("inclock-4", mi), ("inclock-4", mi),
    ]
    svc = CheckService(
        batch_size=512,
        table_log2=16,
        **(
            {"store": "tiered", "high_water": 0.7, "summary_log2": 16}
            if tiered
            else {}
        ),
    )
    t0 = time.monotonic()
    handles = [(name, svc.submit(m)) for name, m in jobs]
    svc.drain(timeout=600)
    sec = time.monotonic() - t0

    failures = []
    for name, h in handles:
        r = h.result()
        got = (r.state_count, r.unique_state_count)
        if got != GOLD[name] or not r.complete:
            failures.append(f"job {h.id} ({name}): {got} != {GOLD[name]}")
        print(
            f"job {h.id} {name}: states={r.state_count} "
            f"unique={r.unique_state_count} steps={r.steps} "
            f"complete={r.complete} metrics={h.metrics()}"
        )
    print(
        f"{len(jobs)} jobs in {sec:.2f}s -> {len(jobs) / sec:.2f} jobs/sec "
        f"({svc.stats()['device_steps']} fused device steps, "
        f"{svc.stats()['groups']} model groups)"
    )
    if tiered:
        print("store:", svc.store_stats())
    svc.close()
    if failures:
        print("FAILURES:", "; ".join(failures), file=sys.stderr)
        return 1
    print("service smoke OK")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
