#!/usr/bin/env python
"""ABD linearizable register example CLI
(ref: examples/linearizable-register.rs:252-334)."""

from _cli import (
    argv_int,
    argv_network,
    argv_str,
    argv_subcommand,
    network_names,
    report,
    thread_count,
)

from stateright_tpu.examples.abd import AbdModelCfg


def main():
    cmd = argv_subcommand()
    if cmd == "check":
        client_count = argv_int(2, 2)
        network = argv_network(3)
        print(f"Model checking a linearizable register with {client_count} clients.")
        report(
            AbdModelCfg(client_count=client_count, server_count=3, network=network)
            .into_model()
            .checker()
            .threads(thread_count())
            .spawn_dfs()
        )
    elif cmd == "check-tpu":
        client_count = argv_int(2, 2)
        if client_count > 2:
            print(
                "The device check is sized for at most 2 clients (2 clients "
                "reach 41,138,199 unique states, a 2^27 table); for more, "
                "check on the host (check)."
            )
            return
        print(
            f"Model checking a linearizable register with {client_count} "
            "clients on the device frontier checker."
        )
        from stateright_tpu.tensor.abd import TensorAbd

        size = (
            dict(batch_size=1024, table_log2=14)
            if client_count < 2
            else dict(batch_size=8192, table_log2=27, queue_log2=26)
        )
        report(TensorAbd(client_count=client_count).checker().spawn_tpu(**size))
    elif cmd == "explore":
        client_count = argv_int(2, 2)
        address = argv_str(3, "localhost:3000")
        network = argv_network(4)
        print(
            f"Exploring state space for linearizable register with "
            f"{client_count} clients on {address}."
        )
        AbdModelCfg(
            client_count=client_count, server_count=3, network=network
        ).into_model().checker().serve(address, block=True)
    elif cmd == "spawn":
        from stateright_tpu.actor import Id
        from stateright_tpu.actor.spawn import spawn
        from stateright_tpu.examples.abd import AbdActor

        port = 3000
        print("  A server that implements a linearizable register.")
        print(f"  Interact via UDP JSON, e.g. nc -u localhost {port}")
        from stateright_tpu.actor.register import Get, GetOk, Internal, Put, PutOk
        from stateright_tpu.examples.abd import AckQuery, AckRecord, Query, Record

        ids = [Id.from_addr("127.0.0.1", port + i) for i in range(3)]
        spawn(
            [
                (ids[i], AbdActor([pid for pid in ids if pid != ids[i]]))
                for i in range(3)
            ],
            msg_types=[
                Put, Get, PutOk, GetOk, Internal,
                Query, AckQuery, Record, AckRecord,
            ],
        )
    else:
        print("USAGE:")
        print("  ./linearizable_register.py check [CLIENT_COUNT] [NETWORK]")
        print("  ./linearizable_register.py check-tpu [CLIENT_COUNT<=2]")
        print("  ./linearizable_register.py explore [CLIENT_COUNT] [ADDRESS] [NETWORK]")
        print("  ./linearizable_register.py spawn")
        print(f"NETWORK: {network_names()}")


if __name__ == "__main__":
    main()
