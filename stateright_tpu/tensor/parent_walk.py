"""Parent chains of discovery paths, looked up in the visited table where
the search left it.

A path needs one parent lookup per state on it (a few dozen at most), so
the walk matches each fingerprint against the table's slots instead of
decoding every visited entry into a host dict: on the chips, one compiled
`while_loop` (`jit_parent_walk`) a dispatch that returns up to `CHAIN`
fingerprints in one small transfer; in host arrays (a chunked four-chip
run's snapshot), the same match in numpy. It reads the slots
`SearchHost.build_parent_map` decodes (`t_lo != 0`; the kv layout's bucket
rows split into their lo and hi halves), so every insert variant and table
layout is served alike, and where a key sits in several slots the last one
wins, as in that dict. On a sharded table each chip matches in its own
shard and a `pmax`/`psum` picks the owner's answer: no table leaves a chip.

The compiled walk depends on the table's layout and shape, the mesh and
`CHAIN`, never on the model, so `compile_cache.WALKS` keeps one per such key
for every check of the process.
"""

from __future__ import annotations

from typing import Callable

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..compile_cache import WALKS
from .fingerprint import pack_fp

#: Fingerprints one dispatch writes; a longer chain takes another dispatch.
CHAIN = 64

_LO = (1 << 32) - 1


def slot_keys(t_lo, t_hi, kv_bucket: int):
    """The table's keys as flat slot-aligned (lo, hi) arrays: the kv
    layout's one array of [lo | hi] bucket rows split into its halves."""
    if kv_bucket:
        kv = t_lo.reshape(-1, 2 * kv_bucket)
        return kv[:, :kv_bucket].reshape(-1), kv[:, kv_bucket:].reshape(-1)
    return t_lo.reshape(-1), t_hi.reshape(-1)


def _lookup(k_lo, k_hi, p_lo, p_hi, lo, hi):
    """(found, parent lo, parent hi) of the last slot holding (lo, hi).
    lo == 0 is the empty-slot sentinel, so such a key is never found."""
    slots = jnp.arange(k_lo.shape[0], dtype=jnp.int32)
    slot = jnp.max(jnp.where((k_lo == lo) & (k_hi == hi), slots, -1))
    at = jnp.maximum(slot, 0)
    return (slot >= 0) & (lo != 0), p_lo[at], p_hi[at]


def _walk(lookup, lo, hi, chain: int):
    """Write (lo, hi) and then each parent `lookup` finds into a chain
    buffer, until parent 0, a key in no slot, or a full buffer. Returns
    (fingerprints written, the next fingerprint, the buffer's lo, hi)."""

    def more(c):
        n, lo, hi, _, _ = c
        return (n < chain) & ((lo | hi) != 0)

    def step(c):
        n, lo, hi, out_lo, out_hi = c
        found, p_lo, p_hi = lookup(lo, hi)
        zero = jnp.uint32(0)
        return (
            n + 1,
            jnp.where(found, p_lo, zero),
            jnp.where(found, p_hi, zero),
            out_lo.at[n].set(lo),
            out_hi.at[n].set(hi),
        )

    buf = jnp.zeros(chain, jnp.uint32)
    return lax.while_loop(more, step, (jnp.int32(0), lo, hi, buf, buf))


class _Walk:
    """One compiled parent walk and how often it has been traced."""

    def __init__(self, kv_bucket: int, mesh, axis, chain: int):
        self.traces = 0

        def parent_walk(t_lo, t_hi, p_lo, p_hi, lo, hi):
            self.traces += 1  # runs only while tracing
            k_lo, k_hi = slot_keys(t_lo, t_hi, kv_bucket)
            flat = (k_lo, k_hi, p_lo.reshape(-1), p_hi.reshape(-1))
            if mesh is None:
                return _walk(lambda a, b: _lookup(*flat, a, b), lo, hi, chain)

            def owner_lookup(a, b):
                found, p_lo, p_hi = _lookup(*flat, a, b)
                me = lax.axis_index(axis)
                owner = lax.pmax(jnp.where(found, me, -1), axis)
                mine = me == owner
                parent = lax.psum(
                    jnp.where(mine, jnp.stack([p_lo, p_hi]), jnp.uint32(0)),
                    axis,
                )
                return owner >= 0, parent[0], parent[1]

            return _walk(owner_lookup, lo, hi, chain)

        if mesh is None:
            self.run = jax.jit(parent_walk)
        else:
            shard = P(axis)
            self.run = jax.jit(jax.shard_map(
                parent_walk, mesh=mesh,
                in_specs=(shard,) * 4 + (P(), P()), out_specs=P(),
                check_vma=False,
            ))


def program_for(tables: tuple, kv_bucket: int) -> _Walk:
    """The compiled walk over these device tables (a shard_map over their
    mesh where they are sharded along one), one a process for each layout,
    table shape, mesh and chain length."""
    sharding = tables[0].sharding
    mesh = axis = None
    if isinstance(sharding, NamedSharding) and sharding.spec:
        mesh, axis = sharding.mesh, sharding.spec[0]
    key = (kv_bucket, tables[0].shape, mesh, axis, CHAIN)
    walk = WALKS.get(key)
    if walk is None:
        walk = _Walk(kv_bucket, mesh, axis, CHAIN)
        WALKS.put(key, walk)
    return walk


def _device_walk(tables: tuple, kv_bucket: int) -> Callable:
    run = program_for(tables, kv_bucket).run

    def walk(fp: int):
        n, nxt_lo, nxt_hi, out_lo, out_hi = jax.device_get(
            run(*tables, np.uint32(fp & _LO), np.uint32(fp >> 32))
        )
        return (
            pack_fp(out_lo[:n], out_hi[:n]).tolist(),
            int(pack_fp(nxt_lo, nxt_hi)),
        )

    return walk


def _host_walk(tables: tuple, kv_bucket: int) -> Callable:
    t_lo, t_hi, p_lo, p_hi = tables
    k_lo, k_hi = slot_keys(t_lo, t_hi, kv_bucket)
    p_lo, p_hi = p_lo.reshape(-1), p_hi.reshape(-1)

    def walk(fp: int):
        out = []
        while fp and len(out) < CHAIN:
            out.append(fp)
            lo, hi = fp & _LO, fp >> 32
            hits = np.flatnonzero((k_lo == lo) & (k_hi == hi)) if lo else ()
            fp = (int(pack_fp(p_lo[hits[-1]], p_hi[hits[-1]]))
                  if len(hits) else 0)
        return out, fp

    return walk


def walk_parents(tables: tuple, fp: int, kv_bucket: int,
                 spill: dict) -> tuple[list, int]:
    """(chain, dispatches): `fp` and its parents, `fp` first, up to a state
    whose parent is 0 (an init state) or in no table; `tables` are
    `(t_lo, t_hi, p_lo, p_hi)` of one table (`[S]`, the kv layout's `t_lo`
    `[2S]`) or of a shard each (`[N, S]`), on the chips or on the host.
    `spill` ({fingerprint: parent}) holds the spill tiers' entries, which
    win over the table's: they carry the original (BFS-discovery) parent,
    which keeps the chain acyclic. `dispatches` counts the table walks."""
    make = _host_walk if isinstance(tables[0], np.ndarray) else _device_walk
    walk = make(tables, kv_bucket)
    chain: list = []
    dispatches = 0
    while fp:
        if fp in spill:
            chain.append(fp)
            fp = spill[fp]
            continue
        part, fp = walk(fp)
        dispatches += 1
        for i in range(1, len(part)):
            if part[i] in spill:  # from here the spill tier's parents rule
                part, fp = part[:i], part[i]
                break
        chain += part
    return chain, dispatches
