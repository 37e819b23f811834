#!/usr/bin/env python
"""Single Decree Paxos example CLI (ref: examples/paxos.rs:354-510)."""

from _cli import (
    argv_int,
    argv_network,
    argv_str,
    argv_subcommand,
    network_names,
    report,
    thread_count,
)

from stateright_tpu.examples.paxos import PaxosModelCfg


def main():
    cmd = argv_subcommand()
    if cmd in ("check", "check-bfs", "check-dfs"):
        client_count = argv_int(2, 2)
        network = argv_network(3)
        print(f"Model checking Single Decree Paxos with {client_count} clients.")
        builder = (
            PaxosModelCfg(client_count=client_count, server_count=3, network=network)
            .into_model()
            .checker()
            .threads(thread_count())
        )
        checker = builder.spawn_dfs() if cmd == "check-dfs" else builder.spawn_bfs()
        report(checker)
    elif cmd == "check-tpu":
        client_count = argv_int(2, 2)
        if client_count > 3:
            print(
                "The hand tensor encoding supports at most 3 clients; for "
                "bigger configs lower the actor model generically "
                "(stateright_tpu.tensor.refine_check or closure='exact')."
            )
            return
        print(
            f"Model checking Single Decree Paxos with {client_count} clients "
            "on the device frontier checker."
        )
        from stateright_tpu.tensor.paxos import TensorPaxos

        batch, table = (2048, 16) if client_count <= 2 else (8192, 22)
        report(
            TensorPaxos(client_count=client_count)
            .checker()
            .spawn_tpu(batch_size=batch, table_log2=table)
        )
    elif cmd == "check-simulation":
        client_count = argv_int(2, 2)
        network = argv_network(3)
        print(f"Model checking Single Decree Paxos with {client_count} clients.")
        report(
            PaxosModelCfg(client_count=client_count, server_count=3, network=network)
            .into_model()
            .checker()
            .threads(thread_count())
            .timeout(10.0)
            .spawn_simulation(0)
        )
    elif cmd == "explore":
        client_count = argv_int(2, 2)
        address = argv_str(3, "localhost:3000")
        network = argv_network(4)
        print(
            f"Exploring state space for Single Decree Paxos with "
            f"{client_count} clients on {address}."
        )
        PaxosModelCfg(
            client_count=client_count, server_count=3, network=network
        ).into_model().checker().serve(address, block=True)
    elif cmd == "spawn":
        from stateright_tpu.actor import Id
        from stateright_tpu.actor.spawn import spawn
        from stateright_tpu.examples.paxos import PaxosActor

        port = 3000
        print("  A set of servers that implement Single Decree Paxos.")
        print("  You can monitor and interact using tcpdump and netcat, e.g.")
        print(f"$ nc -u localhost {port}")
        print('  {"Put": [1, "X"]}')
        print('  {"Get": [2]}')
        from stateright_tpu.actor.register import Get, GetOk, Internal, Put, PutOk
        from stateright_tpu.examples.paxos import (
            Accept,
            Accepted,
            Decided,
            Prepare,
            Prepared,
        )

        ids = [Id.from_addr("127.0.0.1", port + i) for i in range(3)]
        spawn(
            [
                (ids[i], PaxosActor([pid for pid in ids if pid != ids[i]]))
                for i in range(3)
            ],
            msg_types=[
                Put, Get, PutOk, GetOk, Internal,
                Prepare, Prepared, Accept, Accepted, Decided,
            ],
        )
    else:
        print("USAGE:")
        print("  ./paxos.py check-dfs [CLIENT_COUNT] [NETWORK]")
        print("  ./paxos.py check-bfs [CLIENT_COUNT] [NETWORK]")
        print("  ./paxos.py check-simulation [CLIENT_COUNT] [NETWORK]")
        print("  ./paxos.py check-tpu [CLIENT_COUNT<=3]")
        print("  ./paxos.py explore [CLIENT_COUNT] [ADDRESS] [NETWORK]")
        print("  ./paxos.py spawn")
        print(f"NETWORK: {network_names()}")


if __name__ == "__main__":
    main()
