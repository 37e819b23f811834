#!/usr/bin/env python
"""Raft leader election example CLI — the model-zoo workload built for the
device simulation engine (ISSUE 14 / ROADMAP item 5): election safety
(ALWAYS) + leader elected (EVENTUALLY) on a tensor-encoded election protocol
whose bounded-term space explodes combinatorially with the server count.

Small configs (the default `check`) run the exhaustive device frontier
checker against the pinned goldens; `simulate` runs the fourth checker mode —
thousands of continuously-rebatched random walks with a shared visited
table — on spaces the exhaustive engines can't finish (try
`./raft.py simulate 7 7`)."""

from _cli import argv_int, report

from stateright_tpu.core.discovery import HasDiscoveries


def _model(server_count: int, max_term: int):
    from stateright_tpu.tensor.models import TensorRaft

    return TensorRaft(server_count, max_term)


def main():
    import sys

    cmd = sys.argv[1] if len(sys.argv) > 1 else None
    if cmd == "check":
        n = argv_int(2, 3)
        max_term = argv_int(3, 3)
        print(
            f"Checking Raft leader election with {n} servers, "
            f"terms <= {max_term} (exhaustive device frontier checker)."
        )
        report(_model(n, max_term).checker().spawn_tpu())
    elif cmd == "simulate":
        n = argv_int(2, 5)
        max_term = argv_int(3, 5)
        print(
            f"Simulating Raft leader election with {n} servers, "
            f"terms <= {max_term} (device random walks, shared visited "
            "table)."
        )
        report(
            _model(n, max_term)
            .checker()
            .finish_when(HasDiscoveries.ANY)
            .target_state_count(2_000_000)
            .spawn_tpu(
                mode="simulation",
                traces=2048,
                max_depth=256,
                dedup="shared",
                table_log2=22,
            )
        )
    else:
        print("USAGE:")
        print("  ./raft.py check [SERVER_COUNT] [MAX_TERM]")
        print("  ./raft.py simulate [SERVER_COUNT] [MAX_TERM]")


if __name__ == "__main__":
    main()
