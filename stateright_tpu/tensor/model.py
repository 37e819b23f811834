"""The tensor model contract: the device analogue of `Model`.

Where the host `Model` (ref: src/lib.rs:152-257) yields per-state Python
actions, a `TensorModel` defines one batched transition kernel with a STATIC
maximum action fan-out: `expand` maps `[B, lanes] -> ([B, A, lanes], [B, A])`,
where invalid/ignored action slots are masked out. Wasted lanes are fine — the
reference wastes a whole thread on one state at a time.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Hashable, Optional

import jax.numpy as jnp

from ..core.model import Expectation


@dataclass(frozen=True)
class TensorProperty:
    """A vectorized property: `fn(model, states[B, L]) -> bool[B]`."""

    expectation: Expectation
    name: str
    condition: Callable

    @staticmethod
    def always(name, condition) -> "TensorProperty":
        return TensorProperty(Expectation.ALWAYS, name, condition)

    @staticmethod
    def sometimes(name, condition) -> "TensorProperty":
        return TensorProperty(Expectation.SOMETIMES, name, condition)

    @staticmethod
    def eventually(name, condition) -> "TensorProperty":
        return TensorProperty(Expectation.EVENTUALLY, name, condition)


class TensorModel:
    """A transition system over fixed-width uint32 state rows.

    Required: `lanes`, `max_actions`, `init_states()`, `expand(states)`.
    Optional: `properties()`, `within_boundary(states)`, `decode(row)`,
    `action_label(row, action_index)` for human-readable paths, and
    `representative(states) -> states` for symmetry reduction (a batched
    canonicalization kernel; see `stateright_tpu.tensor.symmetry`). When
    defined, the engines fingerprint the canonical form but keep searching
    with the original states (ref: src/checker/dfs.rs:309-334).

    `definition_key()` names the model's definition: a hashable value that
    two instances share exactly when every program traced from them is the
    same (their `lanes`, `max_actions`, `init_states()`, `expand`,
    `properties()`, `within_boundary` and `representative` compute the same
    things). The engines then share one compiled search and one compiled
    path replay across all instances of a definition
    (`stateright_tpu/compile_cache.py`), so a check of a new instance skips
    the trace, the lowering and the executable load. The default, None,
    names nothing: each engine compiles its own search and each instance
    its own replay. Return a key only when it covers everything the traced
    code reads: a key that leaves out a value the code reads hands one
    configuration the compiled program of another, which checks the wrong
    model without any error. `dataclass_definition_key` is the key of a
    dataclass whose `__post_init__` derives every other attribute from its
    fields.
    """

    lanes: int
    max_actions: int
    representative = None  # overridden as a method by symmetric models

    def init_states(self) -> jnp.ndarray:
        """Initial states as uint32[N0, lanes]."""
        raise NotImplementedError

    def expand(self, states: jnp.ndarray):
        """Batched successor generation.

        Args:  states: uint32[B, lanes]
        Returns: (successors uint32[B, max_actions, lanes],
                  valid bool[B, max_actions])
        """
        raise NotImplementedError

    def properties(self) -> list[TensorProperty]:
        return []

    def within_boundary(self, states: jnp.ndarray) -> jnp.ndarray:
        """bool[B]; states outside are not expanded (ref: src/lib.rs:245)."""
        return jnp.ones(states.shape[0], dtype=bool)

    # -- host-side display / parity hooks --------------------------------------

    def decode(self, row) -> Any:
        """Decode one state row (numpy/int tuple) to a human-readable value."""
        return tuple(int(x) for x in row)

    def action_label(self, row, action_index: int) -> Any:
        """Label for taking action slot `action_index` in the state `row`."""
        return action_index

    def format_action(self, action) -> str:
        """Display hook used by `Path.format` (tensor paths carry the
        `action_label` values as their actions)."""
        return str(action)

    def format_step(self, last_state, action) -> Any:
        return None

    def definition_key(self) -> Optional[Hashable]:
        """The model's definition, or None when it cannot be named (see the
        class docstring)."""
        return None

    def property_by_name(self, name: str) -> TensorProperty:
        for p in self.properties():
            if p.name == name:
                return p
        raise KeyError(f"no property named {name!r}")

    def checker(self):
        """Fluent checker config, like `Model.checker()` — `spawn_tpu()` is
        the natural spawn for tensor models."""
        from ..checker.builder import CheckerBuilder

        return CheckerBuilder(self)


def dataclass_definition_key(model, cls) -> Optional[Hashable]:
    """The definition key of a dataclass model of class `cls` whose
    `__post_init__` derives every other attribute (tables, `lanes`,
    `max_actions`, `representative`) from its fields: the class, its code
    as the class and the instance hold it now (so a method replaced at run
    time is another definition), and the field values. None for an
    instance of a subclass, which may read more than the fields: a subclass
    names a definition only by overriding `definition_key()` itself."""
    if type(model) is not cls:
        return None
    code = tuple(
        (name, getattr(fn, "__func__", fn))
        for scope in (*(vars(c) for c in cls.__mro__[:-1]), vars(model))
        for name, fn in scope.items()
        if callable(fn)
    )
    values = tuple(getattr(model, f.name) for f in dataclasses.fields(model))
    return cls, code, values
