#!/usr/bin/env python
"""Fleet load generator: hundreds of concurrent HTTP clients against the
fleet front door, reporting p50/p99 submit→result latency and jobs/s.

The ROADMAP item 1 acceptance harness: starts an N-replica ServiceFleet
behind `serve_fleet`, drives `--clients` threads submitting `--jobs` mixed
jobs (POST /jobs + poll GET /jobs/<id>), honors 503 `Retry-After` backoff,
and verifies every job finished with its golden counts. `--compare` runs
the same load twice — N replicas, then 1 — and prints the jobs/s ratio
(the scale-out claim: N=3 beats N=1 on the mixed set).

    JAX_PLATFORMS=cpu python scripts/fleet_load.py \
        [--replicas 3] [--clients 100] [--jobs 200] [--compare] [--crash] \
        [--warm] [--procs] [--blob]

`--crash` additionally kills one replica mid-load and asserts zero lost
jobs: in-proc through the chaos plane (`fleet.replica_crash`), with
`--procs` by a real `kill -9` of one replica subprocess. `--warm`
pre-publishes the mixed model set into a shared warm-start corpus
(store/corpus.py) and runs the load against it, then runs the SAME load
cold and prints warm-vs-cold jobs/s and p50 side by side (with `--compare`
both modes also get their 1-replica baseline). `--procs` runs the fleet
CROSS-PROCESS (`ServiceFleet(remote=True)`): one `replica_main` subprocess
per replica over a shared store root, with the epoch-fence lease plane on
— the load (and the crash) then exercises real process boundaries.
`--blob` puts the shared store root behind the in-proc object-store
emulator (faults/blobstore.py): checkpoint generations, lease records,
member-discovery records (and the corpus with `--warm`) ride HTTP
conditional puts with bounded-retry/backoff — the true multi-host
storage path under load.
"""

import argparse
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: (registry model name, args, (golden generated, golden unique))
MIX = (
    ("2pc", {"n": 3}, (1_146, 288)),
    ("inclock", {"n": 4}, (257, 257)),
)


def prepublish_corpus(corpus_dir):
    """Pre-publish the mixed model set: one cold submission per model
    through a corpus-enabled 1-replica fleet fills the shared directory
    the warm load then hits."""
    from stateright_tpu.service import ServiceFleet
    from stateright_tpu.service.server import ModelRegistry

    fleet = ServiceFleet(
        n_replicas=1,
        background=True,
        service_kwargs=dict(batch_size=512, table_log2=16),
        corpus_dir=corpus_dir,
    )
    registry = ModelRegistry()
    try:
        handles = [
            fleet.submit(registry.get(name, args)) for name, args, _ in MIX
        ]
        fleet.drain(timeout=600)
        for h in handles:
            h.result()
    finally:
        fleet.close()


def run_load(n_replicas, clients, jobs, crash=False, corpus_dir=None,
             tiered=False, procs=False, blob_root=None):
    from stateright_tpu.faults import FaultPlan, active
    from stateright_tpu.service import ServiceFleet, serve_fleet

    svc_kw = dict(batch_size=512, table_log2=16)
    if tiered or corpus_dir is not None:
        # Warm A/B fairness: the cold side of --warm runs the SAME tiered
        # store config as the corpus side, so the ratio measures the
        # corpus, not the store kind.
        svc_kw["store"] = "tiered"
    fleet_kw = {}
    if blob_root is not None:
        if procs:
            fleet_kw["store_root"] = blob_root
        else:
            # In-proc over the blob backend: the requeue-resume checkpoint
            # plane and the lease fence ride HTTP conditional puts.
            fleet_kw["ckpt_dir"] = blob_root + "/ckpt"
            fleet_kw["lease_dir"] = blob_root + "/leases"
    fleet = ServiceFleet(
        n_replicas=n_replicas,
        background=True,
        max_resident=4,
        service_kwargs=svc_kw,
        corpus_dir=corpus_dir,
        remote=procs,
        **fleet_kw,
    )
    srv = serve_fleet(fleet, address="localhost:0")
    base = "http://" + srv.address
    latencies = []
    failures = []
    lock = threading.Lock()
    per_client = max(jobs // clients, 1)

    def post(path, body):
        req = urllib.request.Request(
            base + path, data=json.dumps(body).encode(), method="POST"
        )
        return json.loads(urllib.request.urlopen(req, timeout=30).read())

    def get(path):
        return json.loads(
            urllib.request.urlopen(base + path, timeout=30).read()
        )

    def client(ci):
        for j in range(per_client):
            name, args, gold = MIX[(ci + j) % len(MIX)]
            t0 = time.monotonic()
            while True:  # submit with Retry-After backoff (503 and 429)
                try:
                    jid = post("/jobs", {"model": name, "args": args})["job"]
                    break
                except urllib.error.HTTPError as e:
                    if e.code not in (503, 429):
                        raise
                    time.sleep(float(e.headers.get("Retry-After") or 1))
            while True:  # poll to completion
                try:
                    p = get(f"/jobs/{jid}")
                except urllib.error.HTTPError as e:
                    if e.code not in (503, 429):
                        raise
                    time.sleep(float(e.headers.get("Retry-After") or 1))
                    continue
                if p["status"] in ("done", "error", "cancelled"):
                    break
                time.sleep(0.01)
            lat = time.monotonic() - t0
            got = (p.get("state_count"), p.get("unique_state_count"))
            with lock:
                latencies.append(lat)
                if p["status"] != "done" or got != gold:
                    failures.append(
                        f"client {ci} job {jid} ({name}): "
                        f"status={p['status']} counts={got} != {gold}"
                    )

    plan = None
    killer = None
    if crash and n_replicas > 1:
        if procs:
            # Cross-process crash: a REAL kill -9 of one replica
            # subprocess mid-load — the router must revoke its lease and
            # requeue from checkpoints, zero lost jobs.
            import signal

            def kill_one():
                time.sleep(1.0)
                try:
                    os.kill(fleet.replicas[0].proc.pid, signal.SIGKILL)
                except OSError:
                    pass

            killer = threading.Thread(target=kill_one, daemon=True)
        else:
            # In-proc: kill one replica a few driver turns in through the
            # chaos plane.
            plan = FaultPlan().rule(
                "fleet.replica_crash", "crash", after=20,
                match={"replica": 0},
            )

    t0 = time.monotonic()
    ctx = active(plan) if plan is not None else None
    if ctx is not None:
        ctx.__enter__()
    if killer is not None:
        killer.start()
    try:
        threads = [
            threading.Thread(target=client, args=(i,)) for i in range(clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        if ctx is not None:
            ctx.__exit__(None, None, None)
    wall = time.monotonic() - t0
    stats = fleet.stats()
    srv.shutdown()
    fleet.close()

    lat_ms = sorted(x * 1000 for x in latencies) or [0.0]

    def pct(q):
        return lat_ms[min(int(q * (len(lat_ms) - 1)), len(lat_ms) - 1)]

    done = len(latencies)
    row = {
        "replicas": n_replicas,
        "clients": clients,
        "jobs": done,
        "sec": round(wall, 2),
        "jobs_per_sec": round(done / max(wall, 1e-9), 2),
        "p50_ms": round(pct(0.50), 1),
        "p99_ms": round(pct(0.99), 1),
        "steals": stats["steals"],
        "requeued": stats["requeued_jobs"],
        "restored": stats["restored_jobs"],
        "replica_crashes": stats["replica_crashes"],
    }
    return row, failures


def run_tenants_load(max_replicas, clients, jobs, slo_ms):
    """Mixed-tenant load against an AUTOSCALING fleet: a quiet 1x tenant
    and a noisy ~10x tenant (with an in-flight quota) share the front
    door; the Autoscaler grows the fleet from its own signals. Reports
    per-tenant p50/p99 and asserts the isolation claims: the quiet
    tenant's p99 stays under `slo_ms`, the noisy tenant's flood trips
    the quota (counted + journaled), and every 429'd submission
    eventually succeeds on retry (the Retry-After contract)."""
    from stateright_tpu.service import ServiceFleet, TenantQuotas, serve_fleet
    from stateright_tpu.service.autoscale import AutoscaleConfig, Autoscaler

    quotas = TenantQuotas()
    quotas.set_quota("noisy", max_in_flight=6)
    fleet = ServiceFleet(
        n_replicas=1,
        background=True,
        max_resident=4,
        service_kwargs=dict(batch_size=512, table_log2=16),
        quotas=quotas,
    )
    auto = Autoscaler(
        fleet,
        AutoscaleConfig(
            min_replicas=1,
            max_replicas=max_replicas,
            queue_high=2.0,
            scale_out_after=2,
            scale_in_after=10,
            cooldown_ticks=4,
        ),
    )
    auto.start(interval_s=0.2)
    srv = serve_fleet(fleet, address="localhost:0")
    base = "http://" + srv.address
    lock = threading.Lock()
    lat = {"quiet": [], "noisy": []}
    rejected = {"quiet": 0, "noisy": 0}
    failures = []

    def post(path, body):
        req = urllib.request.Request(
            base + path, data=json.dumps(body).encode(), method="POST"
        )
        return json.loads(urllib.request.urlopen(req, timeout=30).read())

    def get(path):
        return json.loads(
            urllib.request.urlopen(base + path, timeout=30).read()
        )

    def client(tenant, ci, n_jobs):
        for j in range(n_jobs):
            name, margs, gold = MIX[(ci + j) % len(MIX)]
            t0 = time.monotonic()
            while True:  # submit honoring 503 AND 429 Retry-After
                try:
                    jid = post(
                        "/jobs",
                        {"model": name, "args": margs, "tenant": tenant},
                    )["job"]
                    break
                except urllib.error.HTTPError as e:
                    if e.code not in (503, 429):
                        raise
                    if e.code == 429:
                        with lock:
                            rejected[tenant] += 1
                    time.sleep(float(e.headers.get("Retry-After") or 1))
            while True:  # poll to completion
                try:
                    p = get(f"/jobs/{jid}")
                except urllib.error.HTTPError as e:
                    if e.code not in (503, 429):
                        raise
                    time.sleep(float(e.headers.get("Retry-After") or 1))
                    continue
                if p["status"] in ("done", "error", "cancelled"):
                    break
                time.sleep(0.01)
            got = (p.get("state_count"), p.get("unique_state_count"))
            with lock:
                lat[tenant].append(time.monotonic() - t0)
                if p["status"] != "done" or got != gold:
                    failures.append(
                        f"{tenant} client {ci} job {jid} ({name}): "
                        f"status={p['status']} counts={got} != {gold}"
                    )

    # ~10x asymmetry: the noisy tenant floods, the quiet tenant trickles.
    quiet_jobs = max(jobs // 11, 2)
    noisy_jobs = max(jobs - quiet_jobs, quiet_jobs)
    quiet_clients = max(clients // 10, 1)
    noisy_clients = max(clients - quiet_clients, 1)
    threads = [
        threading.Thread(
            target=client,
            args=("quiet", i, max(quiet_jobs // quiet_clients, 1)),
        )
        for i in range(quiet_clients)
    ] + [
        threading.Thread(
            target=client,
            args=("noisy", i, max(noisy_jobs // noisy_clients, 1)),
        )
        for i in range(noisy_clients)
    ]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t0
    stats = fleet.stats()
    am = auto.metrics()
    auto.close()
    srv.shutdown()
    fleet.close()

    def pct(samples, q):
        s = sorted(x * 1000 for x in samples) or [0.0]
        return round(s[min(int(q * (len(s) - 1)), len(s) - 1)], 1)

    for tenant in ("quiet", "noisy"):
        print(
            f"{tenant}:",
            json.dumps(
                {
                    "jobs": len(lat[tenant]),
                    "p50_ms": pct(lat[tenant], 0.50),
                    "p99_ms": pct(lat[tenant], 0.99),
                    "throttled_429": rejected[tenant],
                }
            ),
        )
    print(
        "autoscale:",
        json.dumps(
            {
                "jobs_per_sec": round(
                    sum(len(v) for v in lat.values()) / max(wall, 1e-9), 2
                ),
                "replicas_high_water": am["replicas_high_water"],
                "scale_outs": am["scale_outs"],
                "scale_ins": am["scale_ins"],
                "quota_rejected": stats["quota_rejected"],
            }
        ),
    )
    quiet_p99 = pct(lat["quiet"], 0.99)
    if quiet_p99 > slo_ms:
        failures.append(
            f"quiet tenant p99 {quiet_p99}ms blew the {slo_ms}ms SLO "
            "(noisy tenant leaked through the isolation)"
        )
    if stats["quota_rejected"] < 1:
        failures.append(
            "noisy flood never tripped its quota (gate not exercised)"
        )
    if max_replicas > 1 and am["replicas_high_water"] < 2:
        failures.append(
            "autoscaler never scaled out under the flood "
            f"(high water {am['replicas_high_water']})"
        )
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--clients", type=int, default=100)
    ap.add_argument("--jobs", type=int, default=200)
    ap.add_argument("--compare", action="store_true",
                    help="also run the same load on 1 replica; print ratio")
    ap.add_argument("--crash", action="store_true",
                    help="kill replica 0 mid-load via the chaos plane")
    ap.add_argument("--warm", action="store_true",
                    help="pre-publish the mixed set into a shared corpus, "
                         "then report warm-vs-cold jobs/s side by side")
    ap.add_argument("--procs", action="store_true",
                    help="cross-process fleet: one replica_main subprocess "
                         "per replica over a shared store root (lease "
                         "plane on; --crash becomes a real kill -9)")
    ap.add_argument("--blob", action="store_true",
                    help="shared store root behind the in-proc object-store "
                         "emulator (blob:// backend: conditional puts, "
                         "bounded retry, member discovery)")
    ap.add_argument("--tenants", action="store_true",
                    help="mixed-tenant isolation run: quiet 1x + noisy 10x "
                         "tenants against an AUTOSCALING fleet (--replicas "
                         "is the autoscaler's max); asserts the quiet "
                         "tenant's p99 SLO and the noisy tenant's quota")
    ap.add_argument("--slo-ms", type=float, default=30_000.0,
                    help="quiet-tenant p99 SLO for --tenants (ms)")
    args = ap.parse_args(argv)

    blobd = None
    roots = [0]

    def fresh_blob_root():
        if blobd is None:
            return None
        roots[0] += 1
        return f"{blobd.root_uri}/load{roots[0]}"

    if args.blob:
        from stateright_tpu.faults.blobstore import serve_blobd

        blobd = serve_blobd()
        print(f"blob emulator at {blobd.root_uri}")

    if args.tenants:
        bad = run_tenants_load(
            args.replicas, args.clients, args.jobs, args.slo_ms
        )
        if blobd is not None:
            blobd.shutdown()
        if bad:
            print("FAILURES:", "; ".join(bad[:10]), file=sys.stderr)
            return 1
        print("tenant load OK")
        return 0

    if args.warm:
        # Warm-vs-cold A/B: pre-publish the mixed set into one shared
        # corpus, run the load against it, then run the identical load
        # cold (same tiered store config) and report side by side. With
        # --compare the 1-replica baseline is ALSO warm (same corpus) so
        # the scale-out ratio stays a replicas-only comparison instead of
        # conflating warm-start speedup into it.
        import tempfile

        with tempfile.TemporaryDirectory(prefix="srtpu-corpus-") as td:
            # With --blob the shared corpus ALSO lives in the object store
            # (content-addressed conditional puts de-duplicate publishes
            # server-side).
            d = td if blobd is None else fresh_blob_root() + "/corpus"
            prepublish_corpus(d)
            row, failures = run_load(
                args.replicas, args.clients, args.jobs, crash=args.crash,
                corpus_dir=d, procs=args.procs,
                blob_root=fresh_blob_root(),
            )
            row1, fail1 = (
                run_load(1, args.clients, args.jobs, corpus_dir=d,
                         procs=args.procs, blob_root=fresh_blob_root())
                if args.compare
                else (None, [])
            )
        cold_row, cold_fail = run_load(
            args.replicas, args.clients, args.jobs, tiered=True,
            procs=args.procs, blob_root=fresh_blob_root(),
        )
        print("warm:", json.dumps(row))
        print("cold:", json.dumps(cold_row))
        ratio = row["jobs_per_sec"] / max(cold_row["jobs_per_sec"], 1e-9)
        print(
            f"warm-start: {row['jobs_per_sec']} jobs/s p50 {row['p50_ms']}ms "
            f"warm vs {cold_row['jobs_per_sec']} jobs/s p50 "
            f"{cold_row['p50_ms']}ms cold -> {ratio:.2f}x"
        )
        bad = list(failures) + cold_fail + fail1
    else:
        row, failures = run_load(
            args.replicas, args.clients, args.jobs, crash=args.crash,
            procs=args.procs, blob_root=fresh_blob_root(),
        )
        print("fleet:", json.dumps(row))
        bad = list(failures)
        row1, fail1 = (
            run_load(1, args.clients, args.jobs, procs=args.procs,
                     blob_root=fresh_blob_root())
            if args.compare
            else (None, [])
        )
        bad += fail1
    if args.compare:
        print("one-replica:", json.dumps(row1))
        ratio = row["jobs_per_sec"] / max(row1["jobs_per_sec"], 1e-9)
        print(
            f"scale-out: {args.replicas} replicas at {row['jobs_per_sec']} "
            f"jobs/s vs 1 replica at {row1['jobs_per_sec']} jobs/s "
            f"-> {ratio:.2f}x"
        )
    if args.crash and row["replica_crashes"] < 1:
        bad.append("crash requested but no replica crash was recorded")
    if blobd is not None:
        blobd.shutdown()
    if bad:
        print("FAILURES:", "; ".join(bad[:10]), file=sys.stderr)
        return 1
    print("fleet load OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
