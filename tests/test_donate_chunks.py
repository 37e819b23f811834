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


_T = 8  # the DUS append's tile in the cases below

# (lanes M, new lanes per append or None for random, fan): n_new at the tile
# edges, none and all new, M not a multiple of the tile (the four-chip
# engine's received block), and the one-chip engine's unexpanded parent
# columns (fan = actions per parent row).
_APPEND_CASES = {
    "random": (24, None, 1),
    "none-new": (24, 0, 1),
    "all-new": (24, 24, 1),
    "n-eq-T": (24, _T, 1),
    "n-eq-T-1": (24, _T - 1, 1),
    "n-eq-T+1": (24, _T + 1, 1),
    "n-eq-2T+1": (24, 2 * _T + 1, 1),
    "ragged-M-random": (20, None, 1),
    "ragged-M-all-new": (20, 20, 1),
    "fan-3": (24, None, 3),
}


def _new_mask(rng, M, n):
    if n is None:
        return rng.random(M) < 0.5
    mask = np.zeros(M, bool)
    mask[rng.choice(M, n, replace=False)] = True
    return mask


@pytest.mark.parametrize("case", list(_APPEND_CASES))
def test_append_variants_agree(case):
    """`append_new_dus` (the TPU default; tiles of `_T` rows gathered by
    lane index) must stay identical to `append_new` (whole-array scatter)
    on the rows that matter, [0, tail), and on the tail, after any append
    sequence."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from stateright_tpu.tensor.frontier import (
        append_new,
        append_new_dus,
        append_rows,
    )

    M, n, fan = _APPEND_CASES[case]
    L, rounds = 3, 4
    Q = rounds * M + append_rows(M, _T)
    dus = jax.jit(partial(append_new_dus, tile=_T, fan=fan))
    scatter = jax.jit(append_new)

    def run(dus_kind, rng):
        q = [jnp.zeros((Q, L), jnp.uint32)] + [
            jnp.zeros(Q, jnp.uint32) for _ in range(4)
        ]
        tail = jnp.int32(0)
        for _ in range(rounds):
            flat = jnp.asarray(rng.integers(1, 99, (M, L), dtype=np.uint32))
            lo = jnp.asarray(rng.integers(1, 99, M, dtype=np.uint32))
            eb = rng.integers(1, 99, M // fan, dtype=np.uint32)
            dp = rng.integers(1, 99, M // fan, dtype=np.uint32)
            new = jnp.asarray(_new_mask(rng, M, n))
            if dus_kind:
                *q, tail = dus(*q, tail, flat, lo, lo + 1, eb, dp, new)
            else:
                eb, dp = np.repeat(eb, fan), np.repeat(dp, fan)
                *q, tail = scatter(*q, tail, flat, lo, lo + 1, eb, dp, new)
        t = int(tail)
        return [np.asarray(a)[:t] for a in q], t

    a, ta = run(False, np.random.default_rng(3))
    b, tb = run(True, np.random.default_rng(3))
    assert ta == tb
    if n is not None:
        assert ta == rounds * n
    for x, y in zip(a, b):
        assert np.array_equal(x, y)


def test_append_dus_at_the_queue_full_guard():
    """At the largest tail the four-chip engine's queue-full guard admits
    (Q - max(append_rows(M, T), K)), an all-new append with M not a
    multiple of the tile fills the queue to its last row: no DUS start
    clamps, so no row already written moves."""
    import jax.numpy as jnp

    from stateright_tpu.tensor.frontier import append_new_dus, append_rows

    M, L, S = 20, 3, 40
    Q = S + append_rows(M, _T)
    tail = Q - max(append_rows(M, _T), _T)
    assert append_rows(M, _T) == 24 and tail + append_rows(M, _T) == Q
    old = np.arange(Q * L, dtype=np.uint32).reshape(Q, L) + 1000
    col = np.arange(Q, dtype=np.uint32) + 1000
    flat = np.arange(M * L, dtype=np.uint32).reshape(M, L) + 1
    lane = np.arange(M, dtype=np.uint32) + 1
    qs, ql, qh, qe, qd, t = append_new_dus(
        jnp.asarray(old), *(jnp.asarray(col) for _ in range(4)),
        jnp.int32(tail), jnp.asarray(flat), jnp.asarray(lane),
        jnp.asarray(lane + 100), jnp.asarray(lane + 200),
        jnp.asarray(lane + 300), jnp.ones(M, bool), tile=_T,
    )
    assert int(t) == tail + M
    assert np.array_equal(np.asarray(qs)[:tail], old[:tail])
    assert np.array_equal(np.asarray(qs)[tail:tail + M], flat)
    for q, off in zip((ql, qh, qe, qd), (0, 100, 200, 300)):
        assert np.array_equal(np.asarray(q)[:tail], col[:tail])
        assert np.array_equal(np.asarray(q)[tail:tail + M], lane + off)


def _tiles_within_bounds(search, r, tile, chips=1):
    """`append_tiles_total` (host-side, from the drained telemetry) lies
    between the steps that claimed anything and ceil(claimed / T) plus one
    partial tile per step and chip."""
    m = search.metrics()
    assert r.detail["telemetry"]["dropped_steps"] == 0
    claimed = search._ring._col("claimed").astype(np.int64)
    assert m["claimed_states"] == claimed.sum()
    assert (claimed > 0).sum() <= m["append_tiles_total"]
    assert m["append_tiles_total"] <= (
        -(-m["claimed_states"] // tile) + r.steps * chips
    )


@pytest.mark.parametrize(
    "model, batch, log2, golden",
    [
        ("2pc4", 256, 13, (8258, 1568)),
        ("paxos2", 2048, 16, (32971, 16668)),
    ],
)
def test_dus_append_goldens(model, batch, log2, golden):
    from stateright_tpu.tensor.paxos import TensorPaxos

    m = (
        TensorTwoPhaseSys(4) if model == "2pc4"
        else TensorPaxos(client_count=2)
    )
    rs = ResidentSearch(m, batch, log2, append="dus")
    r = rs.run()
    assert (r.state_count, r.unique_state_count) == golden
    assert r.complete
    _tiles_within_bounds(rs, r, batch)


def test_sharded_dus_append_golden():
    from stateright_tpu.parallel import ShardedSearch, make_mesh

    ss = ShardedSearch(
        TensorTwoPhaseSys(4), mesh=make_mesh(8), batch_size=128,
        table_log2=11, append="dus",
    )
    r = ss.run()
    assert (r.state_count, r.unique_state_count) == (8258, 1568)
    assert r.complete
    assert sum(r.detail["per_chip_unique"]) == 1568
    _tiles_within_bounds(ss, r, 128, chips=8)


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
