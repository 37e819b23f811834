"""Race the two visited-set insert designs on real hardware.

Usage (one process holds the chip at a time):

    python scripts/race_hashtable.py            # real device (TPU if alive)
    JAX_PLATFORMS=cpu python scripts/race_hashtable.py --cpu

Prints ms/batch and keys/s for the XLA scatter-max insert
(tensor/hashtable.py) vs the partitioned-VMEM Pallas insert
(tensor/pallas_hashtable.py) across bench-relevant (batch, table) shapes,
plus a cross-check that both report the same new-key count. The winner
becomes the engines' default (the loser stays behind the flag).
"""

import argparse
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true", help="pin CPU + interpret")
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args()

    import os

    cpu_requested = args.cpu or "cpu" in [
        p.strip().lower()
        for p in os.environ.get("JAX_PLATFORMS", "").split(",")
    ]
    if cpu_requested:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    from stateright_tpu.compile_cache import use_compile_cache

    use_compile_cache()
    on_cpu = jax.devices()[0].platform == "cpu"
    if on_cpu and not cpu_requested:
        print(
            "WARNING: no accelerator reachable — running on the CPU "
            "backend. These timings are NOT a silicon verdict; do not pick "
            "an engine default from them.",
            flush=True,
        )
    args.cpu = on_cpu  # interpret-mode Pallas + honest labels below
    import jax.numpy as jnp
    import numpy as np

    from stateright_tpu.tensor.hashtable import HashTable
    from stateright_tpu.tensor.pallas_hashtable import PallasHashTable

    print(f"devices: {jax.devices()}", flush=True)
    rng = np.random.default_rng(0)
    rc = 0

    for B, tlog, parts in ((131072, 22, 64), (425984, 25, 256), (425984, 27, 512)):
        batches = []
        for _ in range(args.repeats + 1):
            batches.append(
                (
                    jnp.asarray(rng.integers(1, 2**32, B, dtype=np.uint32)),
                    jnp.asarray(rng.integers(0, 2**32, B, dtype=np.uint32)),
                )
            )
        act = jnp.ones(B, dtype=bool)

        new_counts = {}
        for name, make in (
            ("xla ", lambda: HashTable(tlog)),
            (
                "plas",
                lambda: PallasHashTable(
                    tlog, n_partitions=parts, interpret=args.cpu
                ),
            ),
        ):
            try:
                ht = make()
                lo, hi = batches[0]
                r = ht.insert(lo, hi, lo, hi, act)  # compile + warm
                jax.block_until_ready(r.is_new)
                new_total = int(np.asarray(r.is_new).sum())
                t0 = time.monotonic()
                for lo, hi in batches[1:]:
                    r = ht.insert(lo, hi, lo, hi, act)
                    new_total += int(np.asarray(r.is_new).sum())
                jax.block_until_ready(r.is_new)
                dt = (time.monotonic() - t0) / args.repeats
                new_counts[name] = new_total
                print(
                    f"{name} B={B:>7} table=2^{tlog:<2} "
                    f"{dt * 1e3:8.1f} ms/batch  {B / dt / 1e6:7.2f} Mkeys/s "
                    f"(total new={new_total})",
                    flush=True,
                )
            except Exception as e:  # noqa: BLE001 — a failed variant must
                # not kill the race; the other side's number still matters.
                print(f"{name} B={B} table=2^{tlog} FAILED: {e}", flush=True)
        if len(new_counts) == 2 and len(set(new_counts.values())) != 1:
            # Same batches -> the designs must agree on how many keys were
            # new; a mismatch on real hardware is a correctness bug the
            # interpret-mode parity tests could not see. Loudly disqualify.
            print(
                f"PARITY MISMATCH at B={B} table=2^{tlog}: {new_counts} — "
                "do NOT crown a winner from this run",
                flush=True,
            )
            rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
