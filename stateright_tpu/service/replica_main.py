"""Per-host fleet replica process: `python -m stateright_tpu.service.replica_main`.

One `Replica` driver (crash-only, checkpointing cadence) over one
foreground CheckService, served over HTTP by `remote.serve_replica` and
driven by the in-process driver thread — the subprocess the router's
`RemoteReplica` stub talks to (`ServiceFleet(remote=True)` spawns N of
these over one shared store root, which may be a local/NFS directory or a
``blob://host:port`` object store).

Boot contract (remote.spawn_replica_proc is the other half):

1. acquire the lease the router granted BEFORE spawning us
   (`<root>/leases/lease-replica<idx>.json` — no granted lease is a boot
   failure, not a silent unfenced replica);
2. open the flight-recorder journal behind the lease gate (FencedEvents):
   LOCAL-write under the scratch directory, blob-synced at flush
   boundaries when the root is a blob URI; a REJOINED incarnation
   (``--incarnation <epoch>``) journals under the
   ``replica<idx>@e<epoch>`` writer in its own file, so the restarted
   stream merges cleanly next to the fenced old incarnation's;
3. bind the HTTP server on an ephemeral port and PUBLISH a member record
   (service/discovery.py: address, pid, lease epoch, heartbeat ts) into
   ``<root>/members/`` — the spawner waits for the record whose pid
   matches, the router re-discovers the address from the root alone;
4. HEARTBEAT the record on a ~1 s cadence while the lease is still valid
   — a fenced zombie stops heartbeating instead of lying;
5. drive until SIGTERM (drain + flush) or death by the crash-only rules.

`SR_TPU_FAULTS` in the environment installs a chaos plan in this process,
so cross-process chaos runs replay exactly like in-proc ones.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--idx", type=int, required=True)
    ap.add_argument("--root", required=True,
                    help="shared fleet store root (dir or blob:// URI): "
                         "ckpt/leases/journal/members/...")
    ap.add_argument("--scratch", default=None,
                    help="local dir for logs + local-write journals "
                         "(defaults to --root; REQUIRED for blob roots)")
    ap.add_argument("--service-kwargs", default="{}",
                    help="JSON CheckService kwargs (batch_size, ...)")
    ap.add_argument("--address", default="localhost:0")
    ap.add_argument("--ckpt-every-spins", type=int, default=1)
    ap.add_argument("--pump-rounds", type=int, default=4)
    ap.add_argument("--incarnation", type=int, default=0,
                    help="rejoin respawn marker (the fresh lease epoch): "
                         "journals under replica<idx>@e<epoch>")
    args = ap.parse_args(argv)

    from ..faults.blobstore import is_blob_uri
    from ..faults.plan import FaultPlan, install_plan
    from ..obs import EventJournal
    from .api import CheckService
    from .discovery import MemberDirectory
    from .fleet import Replica
    from .lease import FencedEvents, LeaseStore
    from .remote import serve_replica
    from .router import lease_member

    plan = FaultPlan.from_env()
    if plan is not None:
        install_plan(plan)

    member = lease_member(args.idx)
    root = args.root
    if not is_blob_uri(root):
        root = os.path.abspath(root)
    scratch = args.scratch or root
    if is_blob_uri(scratch):
        raise SystemExit(
            "replica_main needs a local --scratch dir for blob store roots"
        )
    lease_store = LeaseStore(os.path.join(root, "leases"))
    lease = lease_store.acquire(member)  # granted pre-spawn, or boot fails

    writer = member
    jname = f"{member}.jsonl"
    if args.incarnation:
        writer = f"{member}@e{args.incarnation}"
        jname = f"{member}.e{args.incarnation}.jsonl"
    local_journal_dir = os.path.join(scratch, "journal")
    os.makedirs(local_journal_dir, exist_ok=True)
    sync_uri = (
        os.path.join(root, "journal", jname) if is_blob_uri(root) else None
    )
    journal = EventJournal(
        os.path.join(local_journal_dir, jname), writer=writer,
        sync_uri=sync_uri,
    )
    events = FencedEvents(journal, lease)

    kw = json.loads(args.service_kwargs)
    kw["background"] = False  # the Replica driver owns the pumping

    replica = Replica(
        args.idx,
        lambda: CheckService(events=events, **kw),
        ckpt_every_spins=args.ckpt_every_spins,
        pump_rounds=args.pump_rounds,
        events=events,
        lease=lease,
    )

    srv = serve_replica(
        replica, address=args.address, lease_store=lease_store
    )
    port = srv.httpd.server_address[1]
    address = f"http://localhost:{port}"
    # Address discovery: the member record in the store root is the ONE
    # readiness + addressing channel (no port files) — works identically
    # when the root is an object store, which is the whole point.
    directory = MemberDirectory(root)
    directory.publish(member, address, pid=os.getpid(), epoch=lease.epoch)
    print(f"REPLICA_READY member={member} addr={address} "
          f"epoch={lease.epoch}", flush=True)

    done = threading.Event()

    def on_term(_sig, _frame):
        done.set()

    signal.signal(signal.SIGTERM, on_term)
    signal.signal(signal.SIGINT, on_term)

    # Parent-death watchdog + discovery heartbeat: a replica must never
    # outlive its fleet, and its member record must stay fresh only while
    # its lease does — a fenced zombie STOPS heartbeating (its record goes
    # stale instead of lying), which is itself discovery evidence.
    parent0 = os.getppid()

    def watch_parent() -> None:
        while not done.is_set():
            if os.getppid() != parent0:
                done.set()
                return
            try:
                if lease.valid():
                    directory.publish(
                        member, address, pid=os.getpid(), epoch=lease.epoch
                    )
            except OSError:
                pass  # store outage: heartbeat resumes when it does
            done.wait(1.0)

    threading.Thread(target=watch_parent, daemon=True).start()

    replica.start()
    try:
        done.wait()
    finally:
        # Graceful drain: stop the driver, flush the recorder tail, close
        # the service — a SIGTERM'd replica leaves a clean journal.
        replica.close()
        journal.close()
        try:
            srv.httpd.shutdown()
        except Exception:  # noqa: BLE001 — best-effort teardown
            pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
