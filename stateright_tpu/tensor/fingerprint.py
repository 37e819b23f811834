"""On-device state fingerprinting as a PAIR of uint32 lanes.

The host fingerprint (blake2b over a canonical encoding,
stateright_tpu.core.fingerprint) identifies Python states; device states are
uint32 lane rows, identified by two independent 32-bit murmur3-style folds
(= one 64-bit identity). The two fingerprint domains never need to agree —
parity of unique-state counts only requires each encoding to be injective per
model (SURVEY.md §7 "hard parts") — but both honor the same contracts as the
reference's `Fingerprint` (ref: src/lib.rs:340-387): stable across
runs/processes/chips, and nonzero.

Why a u32 pair instead of one u64: TPUs have no native 64-bit integer ALU —
XLA emulates u64 arithmetic with 32-bit pairs — so the hot sort/probe/compare
ops on fingerprints would pay emulation cost on exactly the hardware this
framework targets. All device code handles (lo, hi) pairs; the host packs
them into one Python int (`pack_fp`) only at the API boundary (parent maps,
Explorer URLs, discovery fingerprints).

Sentinel contract: `lo` is forced nonzero, so a (0, *) pair never denotes a
real state — lo==0 marks empty hash-table slots and "no parent" exactly as
the reference's NonZeroU64 fingerprint does (ref: src/lib.rs:341).
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

# murmur3 fmix32 constants (public domain). numpy scalars, NOT jnp: a
# module-level jnp constant would initialize the device backend at import
# time, and claim the chip in a process that only imports the package.
_M1 = np.uint32(0x85EBCA6B)
_M2 = np.uint32(0xC2B2AE35)
_GOLDEN = np.uint32(0x9E3779B9)


def _mix32(h: jnp.ndarray) -> jnp.ndarray:
    h = (h ^ (h >> jnp.uint32(16))) * _M1
    h = (h ^ (h >> jnp.uint32(13))) * _M2
    return h ^ (h >> jnp.uint32(16))


def device_fingerprint(states: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """uint32[B, L] -> (lo uint32[B] nonzero, hi uint32[B])."""
    lo = jnp.full(states.shape[0], jnp.uint32(0x6C078965))
    hi = jnp.full(states.shape[0], jnp.uint32(0xB5297A4D))
    for i in range(states.shape[1]):  # static, small
        lane = states[:, i] + _GOLDEN * jnp.uint32(i + 1)
        lo = _mix32(lo ^ lane)
        hi = _mix32(hi ^ (lane * _M1 + jnp.uint32(i + 0x1B873593)))
    lo = jnp.where(lo == 0, jnp.uint32(1), lo)
    return lo, hi


def pack_fp(lo, hi):
    """Device pair -> host Python int / numpy uint64 (vectorized)."""
    return (np.uint64(np.asarray(hi)) << np.uint64(32)) | np.uint64(
        np.asarray(lo)
    )


def unpack_fp(fp: int) -> tuple[int, int]:
    """Host int -> (lo, hi) pair."""
    return int(fp) & 0xFFFFFFFF, (int(fp) >> 32) & 0xFFFFFFFF


# -- job-salted fingerprints (check service) -----------------------------------
#
# The multi-job check service (stateright_tpu/service/) packs many concurrent
# check jobs into ONE device hash table. Co-resident jobs must never collide
# on identical states, so each job folds a per-job salt into its table keys:
# `salt_fp` is a BIJECTION of the (lo, hi) pair per salt — injectivity within
# a job is preserved exactly (unique-count parity with a standalone run), and
# two jobs checking the same model map the same state to different keys with
# the same 2^-64 accidental-collision odds as any two unrelated states.
#
# The map is an involution (salt_fp(salt_fp(x)) == x), so unsalting a table
# key back to the standalone fingerprint is the same call — discovery
# fingerprints leave the service bit-identical to a single-job run.


def _mix32_int(h: int) -> int:
    """fmix32 over plain Python ints (no numpy overflow warnings)."""
    h &= 0xFFFFFFFF
    h = ((h ^ (h >> 16)) * int(_M1)) & 0xFFFFFFFF
    h = ((h ^ (h >> 13)) * int(_M2)) & 0xFFFFFFFF
    return h ^ (h >> 16)


def job_salt(job_id: int) -> tuple[np.uint32, np.uint32]:
    """Two well-mixed uint32 salt words for a job id (host-side).

    Distinct job ids give distinct salts (fmix32 is a bijection of u32, and
    the two words mix independent streams), and job ids are never reused
    within one service, so co-resident jobs always carry distinct salts."""
    j = int(job_id) & 0xFFFFFFFF
    lo = _mix32_int((j * int(_GOLDEN)) ^ 0x243F6A88)
    hi = _mix32_int((j * int(_M2)) ^ 0x85A308D3)
    return np.uint32(lo), np.uint32(hi)


def salt_fp(lo, hi, salt_lo, salt_hi):
    """Fold a job salt into (lo, hi) fingerprint pairs — array-generic
    (numpy or jax.numpy), traceable, and an involution per salt.

    XOR is the bijection; the one wrinkle is the engine-wide sentinel
    contract (lo == 0 marks empty slots / "no parent"): `lo ^ salt_lo` hits
    zero exactly when lo == salt_lo, so that single point is remapped to
    `salt_lo` — which is otherwise unreachable (it would need lo == 0, and
    real fingerprints are never zero). The remap keeps the map injective
    over nonzero lo, keeps outputs nonzero, and makes the function its own
    inverse, so the same call salts and unsalts."""
    slo = lo ^ salt_lo
    xp = np if isinstance(slo, (np.ndarray, np.generic)) else jnp
    slo = xp.where(slo == 0, salt_lo, slo)
    return slo, hi ^ salt_hi
