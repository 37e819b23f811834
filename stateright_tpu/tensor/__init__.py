"""The TPU-native checker core: batched frontier expansion on device.

This is the performance path that replaces the reference's thread/work-stealing
design (src/checker/bfs.rs + src/job_market.rs) with frontier-synchronous
batched BFS (SURVEY.md §7):

- a state is a fixed-width row of uint32 lanes; a model defines one vectorized
  transition kernel `expand(states) -> (successors, valid_mask)` with the
  action dimension enumerated statically — one `jit` call expands thousands of
  states per step instead of one thread expanding one state at a time;
- fingerprints are 64-bit identities carried as PAIRS of uint32 lanes (TPUs
  have no native 64-bit integer ALU; see tensor/fingerprint.py) computed on
  device; the visited set is a device-resident bucketed hash table in HBM
  whose insert kernel also stores parent fingerprints for TLC-style path
  reconstruction (mirroring the parent pointers at src/checker/bfs.rs:301-315);
- property predicates are vectorized masks; eventually-bits ride along as a
  per-state bitmask lane (src/checker.rs:580-587 semantics preserved);
- multi-chip runs shard the table by fingerprint ownership and exchange
  successors with all_to_all collectives (stateright_tpu.parallel.sharded),
  replacing the job market's work stealing.

Everything is 32-bit on device: no `jax_enable_x64` required (the round-1
design forced it globally and paid u64 emulation tax in every hot op).
"""

from .model import TensorModel, TensorProperty
from .adapter import TensorModelAdapter, as_host_model
from .fingerprint import device_fingerprint, pack_fp, unpack_fp
from .hashtable import HashTable
from .frontier import FrontierSearch, SearchResult
from .lowering import (
    LoweredActorModel,
    LoweringError,
    lower_actor_model,
    refine_check,
)
from .simulation import DeviceSimulation
from .abd import TensorAbd

__all__ = [
    "DeviceSimulation",
    "TensorAbd",
    "TensorModelAdapter",
    "as_host_model",
    "TensorModel",
    "TensorProperty",
    "device_fingerprint",
    "pack_fp",
    "unpack_fp",
    "HashTable",
    "FrontierSearch",
    "SearchResult",
    "LoweredActorModel",
    "LoweringError",
    "lower_actor_model",
    "refine_check",
]
