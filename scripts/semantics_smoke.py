#!/usr/bin/env python
"""Dedup-first semantics smoke: register anchors cold -> optimized ->
corpus-warm, end to end.

CI-shaped: exercises the whole dedup-first verdict plane (ISSUE 13,
stateright_tpu/semantics/{canonical,batch}.py) in one command —

1. COLD: the abd and single-copy register anchors' post-dedup testers
   evaluated through the pre-PR cache-only path (plane disabled).
2. OPTIMIZED: the same testers through the batched plane (canonical
   collapse + witness guidance + native-parallel search) — verdicts must
   be bit-identical and `witness_guided_hits` must be nonzero.
3. CORPUS-WARM: the packed verdict table round-trips through a real
   corpus entry via the check service (publish on a register-model
   submission, verdict preload on the repeat), replaying the cold run's
   result bit-identically with `verdict_preloads > 0`.

Exit code 0 iff every phase agreed.

    JAX_PLATFORMS=cpu python scripts/semantics_smoke.py
"""

import glob
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def collect_testers(model, cap):
    """The anchor's post-dedup batch (shared enumerator — the bench
    BENCH_SEMANTICS worker measures the same batch shape)."""
    from stateright_tpu.semantics.batch import collect_history_testers

    return collect_history_testers(model, cap)[0]


def main() -> int:
    from stateright_tpu.actor import Network
    from stateright_tpu.actor.register import GetOk
    from stateright_tpu.examples.abd import AbdModelCfg
    from stateright_tpu.examples.single_copy_register import (
        NULL_VALUE,
        SingleCopyModelCfg,
    )
    from stateright_tpu.semantics import canonical, clear_serialization_caches
    from stateright_tpu.semantics.batch import evaluate_batch
    from stateright_tpu.semantics.canonical import CACHE
    from stateright_tpu.service import CheckService
    from stateright_tpu.tensor.lowering import lower_actor_model
    from stateright_tpu.tensor.model import TensorProperty

    failures = []
    net = Network.new_unordered_nonduplicating

    # -- phases 1+2: cold vs optimized on the register anchors -----------------
    anchors = {
        "abd-2c2s": AbdModelCfg(
            client_count=2, server_count=2, network=net()
        ).into_model(),
        "single_copy-5c2s": SingleCopyModelCfg(
            client_count=5, server_count=2, network=net()
        ).into_model(),
    }
    for name, model in anchors.items():
        testers = collect_testers(model, 3000)
        clear_serialization_caches()
        prev = canonical.set_enabled(False)
        t0 = time.monotonic()
        cold = [t.serialized_history() is not None for t in testers]
        cold_sec = time.monotonic() - t0
        canonical.set_enabled(prev)

        clear_serialization_caches()
        guided0 = CACHE.counters["witness_guided_hits"]
        t0 = time.monotonic()
        optimized = evaluate_batch(testers)
        opt_sec = time.monotonic() - t0
        guided = CACHE.counters["witness_guided_hits"] - guided0
        ok = optimized == cold
        print(
            f"[{name}] n={len(testers)} cold={cold_sec:.3f}s "
            f"optimized={opt_sec:.3f}s "
            f"speedup={cold_sec / max(opt_sec, 1e-9):.2f}x "
            f"guided={guided} identical={ok}"
        )
        if not ok:
            failures.append(f"{name}: optimized verdicts != cold verdicts")
        if guided == 0:
            failures.append(f"{name}: witness_guided_hits == 0")

    # -- phase 3: corpus-warm through the check service ------------------------
    def lowered_register():
        cfg = SingleCopyModelCfg(client_count=2, server_count=1)

        def properties(view):
            lin = view.history_pred(lambda h: h.is_consistent())
            chosen = view.any_env(
                lambda env: isinstance(env.msg, GetOk)
                and env.msg.value != NULL_VALUE
            )
            return [
                TensorProperty.always("linearizable", lambda m, s: lin(s)),
                TensorProperty.sometimes(
                    "value chosen", lambda m, s: chosen(s)
                ),
            ]

        return lower_actor_model(cfg.into_model(), properties=properties)

    with tempfile.TemporaryDirectory(prefix="srtpu-semantics-") as corpus_dir:
        clear_serialization_caches()
        svc = CheckService(
            batch_size=128, table_log2=14, store="tiered",
            summary_log2=16, background=False, corpus_dir=corpus_dir,
        )
        try:
            h = svc.submit(lowered_register())
            svc.drain(timeout=600)
            cold_r = h.result()
            entries = glob.glob(os.path.join(corpus_dir, "corpus-*.npz"))
            if not cold_r.detail["corpus"]["published"] or not entries:
                failures.append("corpus: cold run did not publish an entry")

            # "Fresh process": empty verdict caches, fresh lowering.
            clear_serialization_caches()
            guided0 = CACHE.counters["witness_guided_hits"]
            model2 = lowered_register()
            guided = CACHE.counters["witness_guided_hits"] - guided0
            clear_serialization_caches()
            h = svc.submit(model2)
            svc.drain(timeout=600)
            warm_r = h.result()
            cd = warm_r.detail["corpus"]
            print(
                f"[service] warm_start={cd['warm_start']} "
                f"verdict_preloads={cd['verdict_preloads']} "
                f"lowering_guided={guided}"
            )
            if guided + cd["verdict_preloads"] <= 0:
                failures.append(
                    "corpus: witness_guided_hits + verdict_preloads == 0"
                )
            same = (
                warm_r.state_count, warm_r.unique_state_count,
                warm_r.max_depth, sorted(warm_r.discoveries.items()),
            ) == (
                cold_r.state_count, cold_r.unique_state_count,
                cold_r.max_depth, sorted(cold_r.discoveries.items()),
            )
            if not same:
                failures.append("corpus: warm result != cold result")
        finally:
            svc.close()

    if failures:
        for f in failures:
            print(f"FAIL: {f}")
        return 1
    print("semantics smoke: all phases identical, plane live")
    return 0


if __name__ == "__main__":
    sys.exit(main())
