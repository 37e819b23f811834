"""Peaks of the chips the benchmark may run on, and the least bytes a
state-space search must move. Both live here, with the benchmark, so that
no PR that claims a gain can change them.

PEAK_BYTES_PER_S is keyed by `jax.devices()[0].device_kind`; a kind that
is not listed is an error, never a default. Copied from
stateright_tpu/tensor/costmodel.py BY_DEVICE_KIND (PR 21's tree), whose
v5e row cites its source."""

from __future__ import annotations

#: HBM bandwidth, bytes/s. "TPU v5 lite": Google Cloud documentation,
#: "TPU v5e" — 16 GB of HBM at 819 GB/s.
PEAK_BYTES_PER_S = {"TPU v5 lite": 819e9}

#: Bytes of one state fingerprint (the program dedups by 64-bit ones).
FP_BYTES = 8


def peak_bytes_per_s(device_kind: str) -> float:
    try:
        return PEAK_BYTES_PER_S[device_kind]
    except KeyError:
        raise ValueError(f"no peak bandwidth for device kind {device_kind!r};"
                         f" known: {sorted(PEAK_BYTES_PER_S)}") from None


def least_bytes(generated: int, unique: int, lanes: int) -> int:
    """The least bytes any implementation of the search must move through
    device memory, whatever its insert or layout:

    - per generated successor, one probe of the visited table: its
      fingerprint (FP_BYTES read);
    - per unique state, its write to the frontier queue and its read back
      when it is expanded (4 * lanes bytes each, the configuration's state
      width in 32-bit lanes), and the write of its fingerprint into the
      table (FP_BYTES).

    Successors are computed from the frontier row on the chip and need not
    touch memory otherwise. The bound is a bytes bound: the search does
    almost no arithmetic per byte."""
    state = 4 * lanes
    return generated * FP_BYTES + unique * (2 * state + FP_BYTES)
