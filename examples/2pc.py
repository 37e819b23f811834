#!/usr/bin/env python
"""Two-phase commit example CLI (ref: examples/2pc.rs:172-253)."""

from _cli import argv_int, argv_str, argv_subcommand, report, thread_count

from stateright_tpu.examples.two_phase_commit import TwoPhaseSys


def main():
    cmd = argv_subcommand()
    if cmd == "check":
        rm_count = argv_int(2, 2)
        print(f"Checking two phase commit with {rm_count} resource managers.")
        report(
            TwoPhaseSys(rm_count).checker().threads(thread_count()).spawn_dfs()
        )
    elif cmd == "check-bfs":
        rm_count = argv_int(2, 2)
        print(f"Checking two phase commit with {rm_count} resource managers.")
        report(
            TwoPhaseSys(rm_count).checker().threads(thread_count()).spawn_bfs()
        )
    elif cmd == "check-tpu":
        rm_count = argv_int(2, 2)
        print(
            f"Checking two phase commit with {rm_count} resource managers "
            "on the device frontier checker."
        )
        from stateright_tpu.tensor.models import TensorTwoPhaseSys

        report(TensorTwoPhaseSys(rm_count).checker().spawn_tpu())
    elif cmd == "check-sym":
        rm_count = argv_int(2, 2)
        print(
            f"Checking two phase commit with {rm_count} resource managers "
            "using symmetry reduction."
        )
        report(
            TwoPhaseSys(rm_count)
            .checker()
            .threads(thread_count())
            .symmetry()
            .spawn_dfs()
        )
    elif cmd == "explore":
        rm_count = argv_int(2, 2)
        address = argv_str(3, "localhost:3000")
        print(
            f"Exploring state space for two phase commit with {rm_count} "
            f"resource managers on {address}."
        )
        TwoPhaseSys(rm_count).checker().serve(address, block=True)
    else:
        print("USAGE:")
        print("  ./2pc.py check [RESOURCE_MANAGER_COUNT]")
        print("  ./2pc.py check-bfs [RESOURCE_MANAGER_COUNT]")
        print("  ./2pc.py check-tpu [RESOURCE_MANAGER_COUNT]")
        print("  ./2pc.py check-sym [RESOURCE_MANAGER_COUNT]")
        print("  ./2pc.py explore [RESOURCE_MANAGER_COUNT] [ADDRESS]")


if __name__ == "__main__":
    main()
