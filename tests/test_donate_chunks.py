"""donate_chunks: the chunked-dispatch donation mode (round 4) — XLA updates
the carry in place instead of copying the multi-GB table/queue state per
dispatch (~300s/dispatch at table 2^27 on CPU in round 4). Contract under test: identical results to the
non-donated engine, resumability across run() calls, and the documented
overflow trade (no recovery carry)."""

import numpy as np
import pytest

from stateright_tpu.tensor.models import TensorTwoPhaseSys
from stateright_tpu.tensor.resident import ResidentSearch


def test_donated_chunked_run_matches_goldens():
    rs = ResidentSearch(TensorTwoPhaseSys(4), 256, 13, donate_chunks=True)
    seen = []
    r = rs.run(budget=4, progress=lambda sc, uc, md: seen.append(sc))
    assert (r.state_count, r.unique_state_count) == (8258, 1568)
    assert r.complete
    assert set(r.discoveries) == {"abort agreement", "commit agreement"}
    assert len(seen) > 1  # really ran in multiple donated dispatches


def test_donated_run_resumes_across_run_calls():
    rs = ResidentSearch(TensorTwoPhaseSys(4), 256, 13, donate_chunks=True)
    r1 = rs.run(budget=3, max_steps=3)
    assert not r1.complete
    r2 = rs.run(budget=1 << 20)  # resume the suspended donated carry
    assert (r2.state_count, r2.unique_state_count) == (8258, 1568)
    assert r2.complete


def test_sharded_donated_chunked_run_matches_goldens():
    from stateright_tpu.parallel import ShardedSearch, make_mesh

    ss = ShardedSearch(
        TensorTwoPhaseSys(4),
        mesh=make_mesh(8),
        batch_size=128,
        table_log2=11,
        donate_chunks=True,
    )
    r = ss.run(budget=4)
    assert (r.state_count, r.unique_state_count) == (8258, 1568)
    assert r.complete
    assert sum(r.detail["per_chip_unique"]) == 1568


def test_append_variants_agree():
    """`append_new_dus` (kept for a TPU re-race; round 4 found
    scatter wins on CPU) must stay semantically identical to `append_new`
    on the rows that matter: [0, tail) after any append sequence."""
    import jax.numpy as jnp

    from stateright_tpu.tensor.frontier import append_new, append_new_dus

    Q, L, M = 64, 3, 8

    def run(append, rng):
        qs = jnp.zeros((Q, L), jnp.uint32)
        ql = jnp.zeros(Q, jnp.uint32)
        qh = jnp.zeros(Q, jnp.uint32)
        qe = jnp.zeros(Q, jnp.uint32)
        qd = jnp.zeros(Q, jnp.uint32)
        tail = jnp.int32(0)
        for _ in range(4):
            flat = jnp.asarray(rng.integers(1, 99, (M, L), dtype=np.uint32))
            lo = jnp.asarray(rng.integers(1, 99, M, dtype=np.uint32))
            hi = lo + 1
            eb = jnp.zeros(M, jnp.uint32)
            dp = jnp.ones(M, jnp.uint32)
            new = jnp.asarray(rng.random(M) < 0.5)
            qs, ql, qh, qe, qd, tail = append(
                qs, ql, qh, qe, qd, tail, flat, lo, hi, eb, dp, new
            )
        t = int(tail)
        return (
            np.asarray(qs)[:t],
            np.asarray(ql)[:t],
            np.asarray(qh)[:t],
            np.asarray(qe)[:t],
            np.asarray(qd)[:t],
            t,
        )

    a = run(append_new, np.random.default_rng(3))
    b = run(append_new_dus, np.random.default_rng(3))
    assert a[5] == b[5]
    for x, y in zip(a[:5], b[:5]):
        assert np.array_equal(x, y)


def test_whole_search_overflow_invalidates_snapshot():
    # Non-donated whole-search overflow: the failed run's tables are unsound
    # and any previous snapshot must not serve this run's paths (round-4
    # alignment of resident with sharded overflow semantics).
    rs = ResidentSearch(TensorTwoPhaseSys(5), 256, 7)
    with pytest.raises(RuntimeError, match="hash table or queue full"):
        rs.run()
    assert rs._last_tables is None
    with pytest.raises(RuntimeError, match="no table snapshot"):
        rs.reconstruct_path(1)


def test_chunked_overflow_keeps_boundary_snapshot():
    # Non-donated chunked overflow: the carry is kept at the last sound
    # boundary AND the reconstruction snapshot points at that same boundary.
    rs = ResidentSearch(TensorTwoPhaseSys(5), 256, 7)
    with pytest.raises(RuntimeError, match="checkpoint"):
        rs.run(budget=4)
    assert rs._carry is not None
    assert rs._last_tables is not None  # the boundary tables, not stale/None


@pytest.mark.slow
def test_sharded_donated_overflow_has_no_recovery_carry():
    # Slow-marked (tier-1 870s budget): the donate-specific overflow
    # contract (no carry, no snapshot, actionable message) is pinned
    # fast-tier by the resident twin below; this re-proves it across the
    # 8-chip mesh.
    from stateright_tpu.parallel import ShardedSearch, make_mesh

    ss = ShardedSearch(
        TensorTwoPhaseSys(5),
        mesh=make_mesh(8),
        batch_size=128,
        table_log2=8,  # 256 slots/chip * 8 chips << 8,832 uniques
        donate_chunks=True,
    )
    with pytest.raises(RuntimeError, match="donate_chunks=True"):
        ss.run(budget=8)
    assert ss._carry is None
    with pytest.raises(RuntimeError, match="no table snapshot"):
        ss.reconstruct_path(1)


def test_donated_overflow_has_no_recovery_carry():
    # Table far too small: overflow must raise the donate-specific message
    # (the non-donated engine instead keeps the pre-chunk carry for
    # checkpoint-then-regrow; tests/test_checkpoint.py covers that path).
    rs = ResidentSearch(TensorTwoPhaseSys(5), 256, 7, donate_chunks=True)
    with pytest.raises(RuntimeError, match="donate_chunks=True"):
        rs.run(budget=8)
    assert rs._carry is None
    with pytest.raises(RuntimeError, match="no table snapshot"):
        rs.reconstruct_path(1)


def test_append_variants_identical_results():
    # The backend-informed default picks scatter on CPU; pin both variants
    # explicitly and require identical counts, discoveries, and completion
    # (the DUS path is the TPU default — round-4: 627k -> 1.06M states/s).
    from stateright_tpu.tensor.resident import ResidentSearch

    runs = {
        v: ResidentSearch(
            TensorTwoPhaseSys(4), 256, 14, append=v
        ).run()
        for v in ("scatter", "dus")
    }
    a, b = runs["scatter"], runs["dus"]
    assert (a.state_count, a.unique_state_count, a.max_depth) == (
        b.state_count,
        b.unique_state_count,
        b.max_depth,
    )
    assert a.discoveries.keys() == b.discoveries.keys()
    assert a.complete and b.complete
