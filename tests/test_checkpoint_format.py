"""The checkpoint file formats of the resident and the sharded engines,
pinned: the archive's array names, each array's shape and dtype, and the
meta record's keys. The resident file carries no shard axis (`spill_fps`,
one store record); the sharded file leads every carry array with the chip
axis (`spill_fps_<i>`, one store record per shard). A file written by an
earlier version of either engine must keep loading, so these are literals,
not values read back from the engine under test."""

import json

import pytest

from stateright_tpu.faults.ckptio import load_latest
from stateright_tpu.parallel import ShardedSearch, make_mesh
from stateright_tpu.tensor.models import TensorTwoPhaseSys
from stateright_tpu.tensor.resident import ResidentSearch

GOLD_2PC4 = (8258, 1568)
L, P, TMR = 7, 3, 4096  # 2pc-4 lanes, property count, telemetry ring rows

RESIDENT_META = {
    "lanes", "max_actions", "properties", "table_log2", "queue_log2",
    "batch_size", "table_layout", "insert_variant", "store", "q_compacted",
    "abort_reason",
}
SHARDED_META = {
    "lanes", "max_actions", "properties", "table_log2", "batch_size",
    "n_chips", "dest_capacity", "insert_variant", "store", "q_compacted",
}
STORE_META = {
    "high_water", "low_water", "summary_log2", "summary_hashes",
    "spill_events",
}
TIERED = dict(store="tiered", summary_log2=14, high_water=0.6)
SHARDED_TIERED = dict(
    store="tiered", summary_log2=12, high_water=0.3, dest_capacity=32
)

#: case -> (shards or None for the resident engine, engine arguments,
#: table slots S, queue rows Q, suspect rows SQ, Bloom words W)
CASES = {
    "resident": (None, dict(batch_size=64, table_log2=12), 4096, 5504, 0, 1),
    "resident-tiered": (
        None, dict(batch_size=32, table_log2=11, **TIERED), 2048, 4864,
        2112, 512,
    ),
    "sharded2": (2, dict(batch_size=32, table_log2=11), 2048, 3456, 0, 1),
    "sharded4": (4, dict(batch_size=32, table_log2=11), 2048, 4096, 0, 1),
    "sharded2-tiered": (
        2, dict(batch_size=4, table_log2=9, **SHARDED_TIERED), 512, 768,
        192, 128,
    ),
}


def _carry_shapes(S, Q, SQ, W) -> dict:
    u32, i32 = "uint32", "int32"
    return {
        "t_lo": ((S,), u32), "t_hi": ((S,), u32),
        "p_lo": ((S,), u32), "p_hi": ((S,), u32),
        "q_states": ((Q, L), u32), "q_lo": ((Q,), u32), "q_hi": ((Q,), u32),
        "q_ebits": ((Q,), u32), "q_depth": ((Q,), u32),
        "head": ((), i32), "tail": ((), i32),
        "gen_lo": ((), u32), "gen_hi": ((), u32),
        "unique_count": ((), i32), "max_depth": ((), u32),
        "discovered": ((), u32), "disc_lo": ((P,), u32), "disc_hi": ((P,), u32),
        "overflow": ((), u32), "steps": ((), i32), "hot_claims": ((), i32),
        "s_states": ((SQ, L), u32), "s_lo": ((SQ,), u32), "s_hi": ((SQ,), u32),
        "s_ebits": ((SQ,), u32), "s_depth": ((SQ,), u32), "s_tail": ((), i32),
        "summary": ((W,), u32), "tm_rows": ((TMR, 8), u32),
    }


def _engine(case):
    n, kw, *_ = CASES[case]
    if n is None:
        return ResidentSearch(TensorTwoPhaseSys(4), **kw)
    return ShardedSearch(TensorTwoPhaseSys(4), mesh=make_mesh(n), **kw)


@pytest.mark.parametrize("case", sorted(CASES))
def test_checkpoint_format_is_pinned_and_resumes(case, tmp_path):
    n, kw, S, Q, SQ, W = CASES[case]
    tiered = kw.get("store") == "tiered"
    eng = _engine(case)
    r = eng.run(budget=1, max_steps=3)
    assert not r.complete
    path = str(tmp_path / "c.npz")
    eng.checkpoint(path)
    data, _src = load_latest(path)

    expected = _carry_shapes(S, Q, SQ, W)
    if n is not None:
        expected = {k: ((n, *s), t) for k, (s, t) in expected.items()}
        expected["cont"] = ((n,), "bool")
    spill = (
        ({"spill_fps", "spill_parents"} if n is None else {
            f"spill_{k}_{i}" for k in ("fps", "parents") for i in range(n)
        })
        if tiered else set()
    )
    assert set(data.files) == set(expected) | spill | {"meta"}
    for k, (shape, dtype) in expected.items():
        assert (data[k].shape, str(data[k].dtype)) == (shape, dtype), k
    for k in spill:
        assert data[k].ndim == 1 and str(data[k].dtype) == "uint64", k

    meta = json.loads(bytes(data["meta"].tobytes()).decode())
    assert set(meta) == (RESIDENT_META if n is None else SHARDED_META)
    if not tiered:
        assert meta["store"] is None
    elif n is None:
        assert set(meta["store"]) == STORE_META
    else:
        assert len(meta["store"]) == n
        assert all(set(m) == STORE_META for m in meta["store"])

    if n is None:
        resumed = ResidentSearch.load_checkpoint(TensorTwoPhaseSys(4), path)
    else:
        resumed = ShardedSearch.load_checkpoint(
            TensorTwoPhaseSys(4), path, mesh=make_mesh(n)
        )
    r = resumed.run()
    assert r.complete
    assert (r.state_count, r.unique_state_count) == GOLD_2PC4
