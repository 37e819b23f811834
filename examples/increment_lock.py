#!/usr/bin/env python
"""Lock-fixed increment example CLI (ref: examples/increment_lock.rs)."""

from _cli import argv_int, argv_str, argv_subcommand, report, thread_count

from stateright_tpu.examples.increment import IncrementLockSys


def main():
    cmd = argv_subcommand()
    if cmd == "check":
        n = argv_int(2, 3)
        print(f"Model checking increment_lock with {n} threads.")
        report(IncrementLockSys(n).checker().threads(thread_count()).spawn_dfs())
    elif cmd == "check-sym":
        n = argv_int(2, 3)
        print(
            f"Model checking increment_lock with {n} threads using symmetry reduction."
        )
        report(
            IncrementLockSys(n)
            .checker()
            .threads(thread_count())
            .symmetry()
            .spawn_dfs()
        )
    elif cmd in ("check-tpu", "check-tpu-sym"):
        n = argv_int(2, 3)
        sym = cmd == "check-tpu-sym"
        print(
            f"Model checking increment_lock with {n} threads on the device "
            f"frontier checker{' using symmetry reduction' if sym else ''}."
        )
        from stateright_tpu.tensor.models import TensorIncrementLock

        report(
            TensorIncrementLock(n, symmetry=sym)
            .checker()
            .spawn_tpu(batch_size=1024, table_log2=14)
        )
    elif cmd == "explore":
        n = argv_int(2, 3)
        address = argv_str(3, "localhost:3000")
        print(
            f"Exploring the state space of increment_lock with {n} threads on {address}."
        )
        IncrementLockSys(n).checker().serve(address, block=True)
    else:
        print("USAGE:")
        print("  ./increment_lock.py check [THREAD_COUNT]")
        print("  ./increment_lock.py check-sym [THREAD_COUNT]")
        print("  ./increment_lock.py check-tpu [THREAD_COUNT]")
        print("  ./increment_lock.py check-tpu-sym [THREAD_COUNT]")
        print("  ./increment_lock.py explore [THREAD_COUNT] [ADDRESS]")


if __name__ == "__main__":
    main()
