"""Two-process `jax.distributed` validation of ShardedSearch.

Each process contributes 4 virtual CPU devices (gloo collectives) and runs
the SAME SPMD program: one 8-device global mesh, one whole-search dispatch.
This proves the `make_mesh` multi-host claim — under
`jax.distributed.initialize()` the engine code is unchanged; the all_to_all
successor shuffle and psum termination ride the cross-process transport
(gloo here; ICI/DCN on real multi-host TPU slices).

Run one process per rank (the test harness does this):

    python scripts/multihost_sharded.py --num-processes 2 --process-id 0 \
        --coordinator 127.0.0.1:19735
    python scripts/multihost_sharded.py --num-processes 2 --process-id 1 \
        --coordinator 127.0.0.1:19735

Each rank prints one JSON line with the global counts; the counts must be
identical on every rank and match the single-process goldens
(2PC-4: 8,258 generated / 1,568 unique — bench.GOLDEN).
"""

import argparse
import json
import os
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--num-processes", type=int, required=True)
    ap.add_argument("--process-id", type=int, required=True)
    ap.add_argument("--coordinator", default="127.0.0.1:19735")
    ap.add_argument("--devices-per-process", type=int, default=4)
    ap.add_argument(
        "--checkpoint",
        default=None,
        help="suspend mid-search, checkpoint to this path (rank 0 writes), "
        "then resume to completion",
    )
    args = ap.parse_args()

    # Env must be set before jax initializes its backends. Any inherited
    # device-count flag (e.g. the test conftest's =8) must be REPLACED, not
    # kept — each rank contributes exactly devices_per_process devices.
    os.environ["JAX_PLATFORMS"] = "cpu"
    kept = [
        f
        for f in os.environ.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    kept.append(
        "--xla_force_host_platform_device_count="
        f"{args.devices_per_process}"
    )
    os.environ["XLA_FLAGS"] = " ".join(kept)

    import jax

    jax.config.update("jax_platforms", "cpu")
    # Cross-process collectives on the CPU backend need a real transport.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        args.coordinator,
        num_processes=args.num_processes,
        process_id=args.process_id,
    )

    from stateright_tpu.parallel import ShardedSearch, make_mesh
    from stateright_tpu.tensor.models import TensorTwoPhaseSys

    n_global = args.num_processes * args.devices_per_process
    assert len(jax.devices()) == n_global, (
        f"expected {n_global} global devices, got {len(jax.devices())}"
    )

    search = ShardedSearch(
        TensorTwoPhaseSys(4),
        mesh=make_mesh(n_global),
        batch_size=256,
        table_log2=12,
    )
    ckpt_exists = None
    if args.checkpoint:
        # Cross-process checkpoint contract: EVERY rank calls checkpoint()
        # (the carry gather is a collective); only process 0 writes.
        from jax.experimental import multihost_utils

        from stateright_tpu.tensor.search_host import _ckpt_path

        r = search.run(budget=6, max_steps=6)  # suspend mid-search
        search.checkpoint(args.checkpoint)
        # Barrier before the existence check: rank 0 returns from
        # checkpoint() only after writing, other ranks return right after
        # the collective gather — without the sync their check races the
        # write.
        multihost_utils.sync_global_devices("ckpt-written")
        ckpt_exists = os.path.exists(_ckpt_path(args.checkpoint))
        r = search.run()  # then finish from the suspended carry
    else:
        r = search.run()
    out = {
        "process_id": args.process_id,
        "num_processes": args.num_processes,
        "global_devices": n_global,
        "local_devices": jax.local_device_count(),
        "generated": r.state_count,
        "unique": r.unique_state_count,
        "max_depth": r.max_depth,
        "complete": r.complete,
        "discoveries": sorted(r.discoveries),
        "per_chip_unique": r.detail["per_chip_unique"],
        "checkpoint_file_exists": ckpt_exists,
    }
    print("MULTIHOST_RESULT " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
