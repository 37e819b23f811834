"""Device-side symmetry reduction tests: canonicalization kernels + golden
counts on all three device engines (host-orchestrated, resident, sharded),
against the reference's symmetry goldens (2PC-5: 8,832 → 665,
ref: examples/2pc.rs:163-168; increment-2: 13 → 8,
ref: examples/increment.rs:32-105) and the host DFS symmetry checker."""

import jax.numpy as jnp
import numpy as np
import pytest

from stateright_tpu.parallel import ShardedSearch, make_mesh
from stateright_tpu.tensor.frontier import FrontierSearch
from stateright_tpu.tensor.models import TensorIncrement, TensorTwoPhaseSys
from stateright_tpu.tensor.resident import ResidentSearch
from stateright_tpu.tensor.symmetry import (
    gather_entities,
    permute_mask_bits,
    stable_argsort,
)


def test_symmetry_helpers():
    keys = jnp.asarray([[3, 1, 2], [2, 2, 1]], dtype=jnp.uint32)
    perm = stable_argsort(keys)
    assert np.array_equal(np.asarray(perm), [[1, 2, 0], [2, 0, 1]])
    lanes = jnp.asarray([[30, 10, 20], [20, 21, 10]], dtype=jnp.uint32)
    assert np.array_equal(
        np.asarray(gather_entities(lanes, perm)), [[10, 20, 30], [10, 20, 21]]
    )
    # mask bits follow the same permutation: new bit j = old bit perm[j].
    mask = jnp.asarray([0b001, 0b011], dtype=jnp.uint32)
    out = np.asarray(permute_mask_bits(mask, perm))
    assert out[0] == 0b100  # entity 0 (set) lands at new slot 2
    assert out[1] == 0b110  # entities {0,1} land at new slots {1, 2}


def test_2pc_representative_is_idempotent_and_orbit_stable():
    m = TensorTwoPhaseSys(3, symmetry=True)
    # Two states in the same orbit: RM states permuted along with their
    # prepared and message bits.
    a = jnp.asarray([[1, 0, 2, 0, 0b001, 0b001]], dtype=jnp.uint32)
    b = jnp.asarray([[0, 2, 1, 0, 0b100, 0b100]], dtype=jnp.uint32)
    ra = np.asarray(m.representative(a))
    rb = np.asarray(m.representative(b))
    assert np.array_equal(ra, rb)
    assert np.array_equal(np.asarray(m.representative(jnp.asarray(ra))), ra)


@pytest.fixture(scope="module")
def tpc5_runs():
    """2PC-5 searches shared by the golden-count and verdict-parity tests —
    each (engine, symmetry) config runs once per module."""
    return {
        "full_frontier": FrontierSearch(TensorTwoPhaseSys(5), 2048, 20).run(),
        "sym_frontier": FrontierSearch(
            TensorTwoPhaseSys(5, symmetry=True), 1024, 16
        ).run(),
        "sym_resident": ResidentSearch(
            TensorTwoPhaseSys(5, symmetry=True), 1024, 16
        ).run(),
        "sym_sharded": ShardedSearch(
            TensorTwoPhaseSys(5, symmetry=True),
            mesh=make_mesh(8),
            batch_size=256,
            table_log2=14,
        ).run(),
    }


@pytest.mark.slow
def test_2pc5_symmetry_golden_all_engines(tpc5_runs):
    # Slow-marked (r22 tier-1 budget trade; the shared tpc5_runs fixture
    # is the heaviest setup in the fast tier). Fast-tier twins: the SAME
    # 314-orbit reduction is cross-validated host-side by
    # test_host_dfs_matches_device_reduction, the 8,832 full space by
    # test_tensor_checker.py::test_2pc_5_golden, and per-engine device
    # symmetry by the increment-lock goldens below.
    # Full space: 8,832 (ref: examples/2pc.rs:158-159). The device
    # full-per-RM-key canonicalization is a true orbit invariant, so its
    # reduced count (314) is traversal-order-independent and STRONGER than the
    # reference's value-only sort (665, which splits orbits on satellite-bit
    # ties and depends on DFS order) — see
    # test_host_dfs_matches_device_reduction for the cross-validation.
    assert tpc5_runs["full_frontier"].unique_state_count == 8832
    assert tpc5_runs["sym_frontier"].unique_state_count == 314
    assert tpc5_runs["sym_resident"].unique_state_count == 314
    assert tpc5_runs["sym_sharded"].unique_state_count == 314


def test_host_dfs_matches_device_reduction():
    """Host DFS using the SAME full-key canonicalization lands on the same
    count as the device engines — the reduction is engine-independent."""
    from stateright_tpu.examples.two_phase_commit import TwoPhaseSys

    checker = (
        TwoPhaseSys(5).checker().symmetry_fn(_full_key_rep).spawn_dfs().join()
    )
    assert checker.unique_state_count() == 314
    checker.assert_properties()


def _full_key_rep(state):
    """Host-side twin of the device full-key canonicalization (independent
    implementation: Python tuples/frozensets vs jnp argsort/gather)."""
    from stateright_tpu.examples.two_phase_commit import TwoPhaseState

    n = len(state.rm_state)
    order = sorted(
        range(n),
        key=lambda i: (
            state.rm_state[i],
            state.tm_prepared[i],
            ("prepared", i) in state.msgs,
        ),
    )
    inv = {old: new for new, old in enumerate(order)}
    return TwoPhaseState(
        rm_state=tuple(state.rm_state[i] for i in order),
        tm_state=state.tm_state,
        tm_prepared=tuple(state.tm_prepared[i] for i in order),
        msgs=frozenset(
            ("prepared", inv[m[1]]) if isinstance(m, tuple) else m
            for m in state.msgs
        ),
    )


@pytest.mark.slow
def test_2pc5_verdict_parity_reduced_vs_unreduced(tpc5_runs):
    """On a space where reduced/unreduced counts diverge
    (2PC-5: 314 vs 8,832), property VERDICTS must be identical — reduction
    only changes which orbit member is stored, never what is proven.
    Discovery semantics: a `sometimes` name present = witnessed (pass); an
    `always` name present = counterexample (fail)."""
    expected = {"abort agreement", "commit agreement"}  # both witnessed,
    # "consistent" (always) violated nowhere.
    assert set(tpc5_runs["full_frontier"].discoveries) == expected
    assert set(tpc5_runs["sym_frontier"].discoveries) == expected
    assert set(tpc5_runs["sym_resident"].discoveries) == expected
    assert set(tpc5_runs["sym_sharded"].discoveries) == expected

    # And on a space with a FAILING always-property (increment race,
    # 13 -> 8): the counterexample survives reduction.
    full_i = FrontierSearch(TensorIncrement(2), 64, 10).run()
    sym_i = FrontierSearch(TensorIncrement(2, symmetry=True), 64, 10).run()
    assert set(full_i.discoveries) == set(sym_i.discoveries) == {"fin"}


def test_value_sort_reduction_is_traversal_order_dependent():
    """Why the device engines use the full-key orbit invariant instead of
    porting the reference's value-only sort (ref:
    src/checker/rewrite_plan.rs:81-107): value-sort 'representatives' split
    orbits on satellite-bit ties, so the reduced count depends on which orbit
    member each traversal reaches first — BFS and DFS disagree. The full-key
    reduction is schedule-independent, which is the only meaningful golden
    for a parallel, batch-order-dependent device search."""
    from collections import deque

    from stateright_tpu.core.fingerprint import fingerprint
    from stateright_tpu.examples.two_phase_commit import TwoPhaseSys

    def search(model, rep, order):
        seen = set()
        q = deque()
        for s in model.init_states():
            fp = fingerprint(rep(s))
            if fp not in seen:
                seen.add(fp)
                q.append(s)
        while q:
            s = q.popleft() if order == "bfs" else q.pop()
            acts = []
            model.actions(s, acts)
            for a in acts:
                ns = model.next_state(s, a)
                if ns is None:
                    continue
                fp = fingerprint(rep(ns))
                if fp not in seen:
                    seen.add(fp)
                    q.append(ns)  # continue from the ORIGINAL state
        return len(seen)

    m = TwoPhaseSys(5)
    value_sort = lambda s: s.representative()  # noqa: E731 — ref value-sort
    assert search(m, value_sort, "dfs") == 665  # the reference DFS golden
    assert search(m, value_sort, "bfs") == 508  # same reduction, BFS order!
    assert search(m, _full_key_rep, "dfs") == 314
    assert search(m, _full_key_rep, "bfs") == 314


@pytest.mark.slow
def test_2pc7_symmetry_at_scale():
    """Device symmetry beyond toys. 2PC-7: 296,448 unique
    full states (cross-validated against the C++ baseline checker:
    generated 2,744,706 / unique 296,448) reduce to 920 full-key orbits,
    cross-validated against an independent host-DFS implementation of the
    same canonicalization. Verdicts identical reduced vs unreduced."""
    from stateright_tpu.examples.two_phase_commit import TwoPhaseSys

    full = FrontierSearch(TensorTwoPhaseSys(7), 8192, 22).run()
    assert (full.state_count, full.unique_state_count) == (2_744_706, 296_448)
    assert full.complete

    sym = FrontierSearch(TensorTwoPhaseSys(7, symmetry=True), 2048, 18).run()
    assert sym.unique_state_count == 920
    assert sym.complete
    assert set(sym.discoveries) == set(full.discoveries) == {
        "abort agreement",
        "commit agreement",
    }

    host = (
        TwoPhaseSys(7).checker().symmetry_fn(_full_key_rep).spawn_dfs().join()
    )
    assert host.unique_state_count() == 920
    host.assert_properties()


def test_increment_goldens_on_device():
    full = FrontierSearch(
        TensorIncrement(2, full_enumeration=True), 64, 10
    ).run()
    assert full.unique_state_count == 13

    sym = FrontierSearch(
        TensorIncrement(2, symmetry=True, full_enumeration=True), 64, 10
    ).run()
    assert sym.unique_state_count == 8

    # The data race is found either way.
    assert "fin" in FrontierSearch(TensorIncrement(2), 64, 10).run().discoveries
    res = ResidentSearch(
        TensorIncrement(2, symmetry=True, full_enumeration=True), 64, 10
    ).run()
    assert res.unique_state_count == 8
    assert "fin" in res.discoveries


def test_symmetric_path_reconstruction():
    fs = FrontierSearch(TensorIncrement(2, symmetry=True), 64, 10)
    r = fs.run()
    path = fs.reconstruct_path(r.discoveries["fin"])
    # The witness is a real executable path ending in a fin violation.
    states = path.states()
    i, threads = states[-1]
    assert sum(1 for (_, pc) in threads if pc == 3) != i


def test_increment_lock_goldens_all_modes():
    """increment_lock (ref: examples/increment_lock.rs): the per-thread
    (t, pc) pair is the ENTIRE per-entity state, so the device full-key sort
    and the host value-sort coincide — device symmetry counts match the host
    check-sym goldens exactly here (unlike 2PC; see tensor/symmetry.py)."""
    from stateright_tpu.examples.increment import IncrementLockSys
    from stateright_tpu.tensor.models import TensorIncrementLock

    for n, full_golden, sym_golden in ((2, 17, 9), (3, 61, 13)):
        host = IncrementLockSys(n).checker().spawn_dfs().join()
        host_sym = IncrementLockSys(n).checker().symmetry().spawn_dfs().join()
        dev = FrontierSearch(TensorIncrementLock(n), 256, 14).run()
        dev_sym = FrontierSearch(
            TensorIncrementLock(n, symmetry=True), 256, 14
        ).run()
        assert host.unique_state_count() == dev.unique_state_count == full_golden
        assert (
            host_sym.unique_state_count()
            == dev_sym.unique_state_count
            == sym_golden
        )
        assert not dev.discoveries  # fin + mutex hold under the lock


def test_increment_lock_6_sym_golden():
    # The BASELINE.json config #4 workload: N=6 with device symmetry
    # (host-DFS-sym cross-validated: 7,825 full -> 25 representatives).
    from stateright_tpu.tensor.models import TensorIncrementLock

    full = FrontierSearch(TensorIncrementLock(6), 2048, 14).run()
    sym = FrontierSearch(TensorIncrementLock(6, symmetry=True), 1024, 12).run()
    assert full.unique_state_count == 7825
    assert sym.unique_state_count == 25


def test_value_sort_device_dfs_reproduces_reference_665():
    """Opt-in reference-parity symmetry ON DEVICE: the
    device value-sort canonicalization kernel, driven in reference DFS
    order, reproduces the published 2PC-5 golden of 665
    (ref: examples/2pc.rs:163-168) — alongside the engines' default
    order-independent full-key 314."""
    from stateright_tpu.tensor.models import TensorTwoPhaseSys
    from stateright_tpu.tensor.symmetry import device_dfs_unique_count

    assert device_dfs_unique_count(TensorTwoPhaseSys(5, symmetry="value")) == 665
    assert device_dfs_unique_count(TensorTwoPhaseSys(5, symmetry=True)) == 314
