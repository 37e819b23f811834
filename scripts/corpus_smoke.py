#!/usr/bin/env python
"""Warm-start corpus smoke (v2): the full delta-proportional re-verification
ladder end to end through the check service, one command, exit 0 iff every
leg held.

v1 legs (exact rung): publish -> warm hit -> corrupt -> cold fallback ->
re-publish -> warm again. Corpus v2 legs: preempt a job mid-run (the cut
publishes the visited prefix + frontier snapshot as a PARTIAL entry), cancel
the parked job, re-submit — the successor warm-starts from the partial and
its completion SUPERSEDES the partial under the same content key; then a
retuned service (different lowering, same definition) re-checks through the
NEAR rung via the family index. Every leg must return the golden counts;
warm legs must take their expected rung (detail["corpus"]["warm_kind"]).

    JAX_PLATFORMS=cpu python scripts/corpus_smoke.py
"""

import glob
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

GOLD_2PC3 = (1_146, 288)

SVC_KW = dict(
    batch_size=256, table_log2=15, store="tiered",
    summary_log2=16, background=False,
)


def _entry_files(corpus_dir):
    """Corpus ENTRY generations (complete + partial), excluding the v2
    near-match family index and the Spec-CI spec index riding in the
    same directory."""
    return [
        p for p in glob.glob(os.path.join(corpus_dir, "corpus-*.npz"))
        if "-family-" not in os.path.basename(p)
        and "-spec-" not in os.path.basename(p)
    ]


def main() -> int:
    from stateright_tpu.service import CheckService
    from stateright_tpu.store.corpus import CorpusStore
    from stateright_tpu.tensor.models import TensorTwoPhaseSys

    model = TensorTwoPhaseSys(3)
    failures = []

    def submit(svc, label, expect_warm, expect_kind=None, m=None):
        t0 = time.monotonic()
        h = svc.submit(m if m is not None else model)
        svc.drain(timeout=600)
        sec = time.monotonic() - t0
        r = h.result()
        corpus = r.detail.get("corpus") or {}
        print(
            f"{label}: states={r.state_count} unique={r.unique_state_count} "
            f"steps={r.steps} sec={sec:.2f} corpus={corpus}"
        )
        if m is None and (r.state_count, r.unique_state_count) != GOLD_2PC3:
            failures.append(f"{label}: counts != {GOLD_2PC3}")
        if corpus.get("warm_start", False) != expect_warm:
            failures.append(
                f"{label}: warm_start={corpus.get('warm_start')} "
                f"(expected {expect_warm})"
            )
        if expect_kind is not None and corpus.get("warm_kind") != expect_kind:
            failures.append(
                f"{label}: warm_kind={corpus.get('warm_kind')} "
                f"(expected {expect_kind})"
            )
        return r

    # -- v1 legs: exact rung + corruption fallback -----------------------------
    with tempfile.TemporaryDirectory(prefix="srtpu-corpus-") as corpus_dir:
        svc = CheckService(corpus_dir=corpus_dir, **SVC_KW)
        r_cold = submit(svc, "cold (publishes)", expect_warm=False)
        if not (r_cold.detail.get("corpus") or {}).get("published"):
            failures.append("cold run did not publish a corpus entry")

        r_warm = submit(
            svc, "warm (corpus hit)", expect_warm=True, expect_kind="exact"
        )
        if r_warm.steps >= r_cold.steps:
            failures.append(
                f"warm run used {r_warm.steps} steps vs cold {r_cold.steps}"
            )
        if r_warm.discoveries != r_cold.discoveries:
            failures.append("warm discoveries != cold discoveries")

        # Corrupt the published entry (one flipped payload byte): the
        # ckptio CRC footer must catch it and the next submission must
        # fall back to a CORRECT cold run, then re-publish.
        from stateright_tpu.faults.ckptio import corrupt_one_byte

        (entry,) = _entry_files(corpus_dir)
        corrupt_one_byte(entry)
        print(f"corrupted one byte of {os.path.basename(entry)}")

        r_corrupt = submit(svc, "corrupt (cold fallback)", expect_warm=False)
        stats = svc.stats().get("corpus") or {}
        print("corpus stats:", stats)
        if stats.get("corrupt_entries", 0) < 1:
            failures.append("corrupted entry was not detected by the CRC")
        if not (r_corrupt.detail.get("corpus") or {}).get("published"):
            failures.append("cold fallback did not re-publish the entry")

        submit(svc, "re-warm (healed corpus)", expect_warm=True)
        svc.close()

    # -- v2 legs: partial publish -> warm continuation -> supersede -> near ----
    with tempfile.TemporaryDirectory(prefix="srtpu-corpus-v2-") as corpus_dir:
        svc = CheckService(
            corpus_dir=corpus_dir, max_resident=1, preempt_steps=2, **SVC_KW
        )
        hA = svc.submit(model)
        for _ in range(4):  # past the preemption budget
            svc.pump()
        key = hA._job.content_key
        hB = svc.submit(TensorTwoPhaseSys(2))  # the waiter that forces a park
        for _ in range(32):
            svc.pump()
            if hA._job.status == "preempted":
                break
        if hA._job.status != "preempted":
            failures.append(f"job never preempted (status {hA._job.status})")
        store = CorpusStore(corpus_dir)
        pe = store.lookup_partial(key)
        if pe is None or pe.complete or pe.frontier is None:
            failures.append("preemption cut did not publish a frontier partial")
        else:
            print(
                f"preempt partial: states={pe.states} "
                f"frontier_rows={pe.frontier['lo'].size} meta={pe.meta}"
            )
        # Cancel the PARKED job: its preemption-time partial (with the
        # frontier) must survive — the shutdown cut must not overwrite it
        # with a frontier-less one.
        hA.cancel()
        svc.drain(timeout=600)  # the 2pc-2 waiter completes
        if store.lookup_partial(key) is None:
            failures.append("cancelling the parked job clobbered its partial")

        # The successor continues from the published prefix and its
        # completion supersedes the partial under the same content key.
        submit(
            svc, "successor (warm from partial)",
            expect_warm=True, expect_kind="partial",
        )
        stats = svc.stats().get("corpus") or {}
        print("corpus stats:", stats)
        if stats.get("partial_publishes", 0) < 1:
            failures.append("partial_publishes counter never moved")
        if stats.get("partial_preloads", 0) < 1:
            failures.append("partial_preloads counter never moved")
        if stats.get("superseded_entries", 0) < 1:
            failures.append("complete publish did not supersede the partial")
        if store.lookup_partial(key) is not None:
            failures.append("superseded partial entry still on disk")
        if store.lookup(key) is None:
            failures.append("successor did not publish the complete entry")
        svc.close()

        # Near-match after a retune: a DIFFERENT lowering (table_log2 + 1)
        # misses the exact rung; the family index serves the same
        # definition's published set through the near rung.
        near_svc = CheckService(
            corpus_dir=corpus_dir, **dict(SVC_KW, table_log2=16)
        )
        submit(
            near_svc, "retuned (warm via near match)",
            expect_warm=True, expect_kind="near",
        )
        near_stats = near_svc.stats().get("corpus") or {}
        print("corpus stats:", near_stats)
        if near_stats.get("near_match_hits", 0) < 1:
            failures.append("near_match_hits counter never moved")
        near_svc.close()

    if failures:
        print("FAILURES:", "; ".join(failures), file=sys.stderr)
        return 1
    print("corpus smoke OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
