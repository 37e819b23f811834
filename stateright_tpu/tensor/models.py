"""Tensor-state encodings of the canonical workloads, built TPU-first: static
action fan-out, branchless lane updates via `where`, everything batched.

These pair with the host models for count-parity testing (the "exact unique
state counts as cross-implementation oracle" strategy, SURVEY.md §4).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

from .model import TensorModel, TensorProperty, dataclass_definition_key


@dataclass
class TensorLinearEquation(TensorModel):
    """a*x + b*y == c (mod 256) — the canonical checker workload
    (ref: src/test_util.rs:140-192). Lanes: [x, y]; actions: IncreaseX,
    IncreaseY. Full space 256*256 = 65,536 states. Its `definition_key()`
    is its class and fields: nothing else is read."""

    a: int
    b: int
    c: int
    lanes = 2
    max_actions = 2

    def definition_key(self):
        return dataclass_definition_key(self, TensorLinearEquation)

    def init_states(self):
        return jnp.zeros((1, 2), dtype=jnp.uint32)

    def expand(self, states):
        x, y = states[:, 0], states[:, 1]
        inc_x = jnp.stack([(x + 1) % 256, y], axis=1)
        inc_y = jnp.stack([x, (y + 1) % 256], axis=1)
        succs = jnp.stack([inc_x, inc_y], axis=1).astype(jnp.uint32)
        valid = jnp.ones((states.shape[0], 2), dtype=bool)
        return succs, valid

    def properties(self):
        def solvable(model, states):
            x, y = states[:, 0], states[:, 1]
            return (model.a * x + model.b * y) % 256 == model.c % 256

        return [TensorProperty.sometimes("solvable", solvable)]

    def decode(self, row):
        return (int(row[0]), int(row[1]))

    def action_label(self, row, action_index):
        return ["IncreaseX", "IncreaseY"][action_index]


# -- 2PC ----------------------------------------------------------------------

# RM states (2 bits each, packed one per lane for simplicity).
_WORKING, _PREPARED, _COMMITTED, _ABORTED = 0, 1, 2, 3
_TM_INIT, _TM_COMMITTED, _TM_ABORTED = 0, 1, 2


@dataclass
class TensorTwoPhaseSys(TensorModel):
    """Two-phase commit (ref: examples/2pc.rs:59-147), tensor-encoded.

    Lanes: [rm_state[0..N], tm_state, tm_prepared_bitmask, msgs_bitmask]
    where msgs bit i = "Prepared{rm=i}" in flight, bit N = Commit,
    bit N+1 = Abort.

    Actions (static slots): 0 = TmCommit, 1 = TmAbort, then per RM:
    [TmRcvPrepared, RmPrepare, RmChooseToAbort, RmRcvCommit, RmRcvAbort].

    Its `definition_key()` is its class and fields: `__post_init__` derives
    `lanes`, `max_actions` and `representative` from them.
    """

    rm_count: int
    # Opt-in like the host builder's .symmetry(). True selects the full-key
    # orbit invariant (the device default — traversal-order-independent,
    # 2PC-5: 314); "value" selects the reference's value-only sort
    # (ref: src/checker/rewrite_plan.rs:81-107), whose reduced count is
    # traversal-order-DEPENDENT — it reproduces the published 665 golden
    # only under reference DFS order (see tensor/symmetry.py
    # device_dfs_unique_count and the module docstring's measured table).
    symmetry: "bool | str" = False

    def __post_init__(self):
        self.lanes = self.rm_count + 3
        self.max_actions = 2 + 5 * self.rm_count
        if self.symmetry == "value":
            self.representative = self._representative_value_sort
        elif self.symmetry:
            self.representative = self._representative

    def definition_key(self):
        return dataclass_definition_key(self, TensorTwoPhaseSys)

    def init_states(self):
        return jnp.zeros((1, self.lanes), dtype=jnp.uint32)

    def expand(self, states):
        n = self.rm_count
        B = states.shape[0]
        rm = states[:, :n]  # [B, n]
        tm = states[:, n]
        prepared_mask = states[:, n + 1]
        msgs = states[:, n + 2]
        commit_bit = jnp.uint32(1 << n)
        abort_bit = jnp.uint32(1 << (n + 1))

        all_prepared = prepared_mask == jnp.uint32((1 << n) - 1)
        tm_init = tm == _TM_INIT

        succ_list = []
        valid_list = []

        def assemble(rm_new, tm_new, prep_new, msgs_new):
            return jnp.concatenate(
                [
                    rm_new.astype(jnp.uint32),
                    tm_new.astype(jnp.uint32)[:, None],
                    prep_new.astype(jnp.uint32)[:, None],
                    msgs_new.astype(jnp.uint32)[:, None],
                ],
                axis=1,
            )

        # TmCommit (ref: 2pc.rs:73-75, 104-107)
        succ_list.append(
            assemble(rm, jnp.full(B, _TM_COMMITTED), prepared_mask, msgs | commit_bit)
        )
        valid_list.append(tm_init & all_prepared)
        # TmAbort (ref: 2pc.rs:76-78, 108-111)
        succ_list.append(
            assemble(rm, jnp.full(B, _TM_ABORTED), prepared_mask, msgs | abort_bit)
        )
        valid_list.append(tm_init)

        for i in range(n):
            rm_bit = jnp.uint32(1 << i)
            rm_i = rm[:, i]
            one_hot = jnp.arange(n) == i  # [n]

            def set_rm(value):
                return jnp.where(one_hot[None, :], jnp.uint32(value), rm)

            # TmRcvPrepared(i) (ref: 2pc.rs:80-82, 101-103)
            succ_list.append(assemble(rm, tm, prepared_mask | rm_bit, msgs))
            valid_list.append(tm_init & ((msgs & rm_bit) != 0))
            # RmPrepare(i) (ref: 2pc.rs:83-85, 112-115)
            succ_list.append(
                assemble(set_rm(_PREPARED), tm, prepared_mask, msgs | rm_bit)
            )
            valid_list.append(rm_i == _WORKING)
            # RmChooseToAbort(i) (ref: 2pc.rs:86-88, 116-118)
            succ_list.append(assemble(set_rm(_ABORTED), tm, prepared_mask, msgs))
            valid_list.append(rm_i == _WORKING)
            # RmRcvCommitMsg(i) (ref: 2pc.rs:89-91, 119-121)
            succ_list.append(assemble(set_rm(_COMMITTED), tm, prepared_mask, msgs))
            valid_list.append((msgs & commit_bit) != 0)
            # RmRcvAbortMsg(i) (ref: 2pc.rs:92-94, 122-124)
            succ_list.append(assemble(set_rm(_ABORTED), tm, prepared_mask, msgs))
            valid_list.append((msgs & abort_bit) != 0)

        succs = jnp.stack(succ_list, axis=1)  # [B, A, L]
        valid = jnp.stack(valid_list, axis=1)  # [B, A]
        return succs, valid

    def properties(self):
        n = self.rm_count

        def rm_all(states, value):
            return jnp.all(states[:, :n] == jnp.uint32(value), axis=1)

        return [
            TensorProperty.sometimes(
                "abort agreement", lambda m, s: rm_all(s, _ABORTED)
            ),
            TensorProperty.sometimes(
                "commit agreement", lambda m, s: rm_all(s, _COMMITTED)
            ),
            TensorProperty.always(
                "consistent",
                lambda m, s: ~(
                    jnp.any(s[:, :n] == jnp.uint32(_ABORTED), axis=1)
                    & jnp.any(s[:, :n] == jnp.uint32(_COMMITTED), axis=1)
                ),
            ),
        ]

    def _representative(self, states):
        """Canonicalize under RM permutation by stable-sorting RMs on their
        FULL per-RM key (state value, prepared bit, in-flight message bit) and
        permuting the satellite bits to match.

        Using the full key makes this a true orbit invariant, so the reduced
        count is deterministic and traversal-order-independent: 8,832 → 314 at
        5 RMs. The reference sorts on the state value alone, which splits
        orbits on satellite-bit ties and yields the weaker, DFS-order-dependent
        665 (ref: examples/2pc.rs:163-168); the host checker reproduces that
        behavior for parity, while the device models take the stronger
        reduction (cross-validated against host DFS with the same full-key
        canonicalization)."""
        n = self.rm_count
        rm = states[:, :n]
        prepared_mask = states[:, n + 1]
        msgs = states[:, n + 2]
        lanes = jnp.arange(n, dtype=jnp.uint32)
        prep_bits = (prepared_mask[:, None] >> lanes) & jnp.uint32(1)
        msg_bits = (msgs[:, None] >> lanes) & jnp.uint32(1)
        keys = rm * jnp.uint32(4) + prep_bits * jnp.uint32(2) + msg_bits
        return self._permute_rms(states, keys)

    def _representative_value_sort(self, states):
        """The reference's value-only sort (ref: examples/2pc.rs:163-168 via
        src/checker/rewrite_plan.rs:81-107): RMs sort on their state value
        alone, ties broken by original index (stable). Satellite-bit ties
        split orbits, so the reduced count depends on traversal order —
        opt-in for reference-golden parity (2PC-5 = 665 under DFS order),
        not the device default."""
        return self._permute_rms(states, states[:, : self.rm_count])

    def _permute_rms(self, states, keys):
        """Apply the RM permutation given per-RM sort keys: sort RM lanes and
        permute the prepared/message bit positions to match."""
        from .symmetry import gather_entities, permute_mask_bits, stable_argsort

        n = self.rm_count
        rm = states[:, :n]
        prepared_mask = states[:, n + 1]
        msgs = states[:, n + 2]
        perm = stable_argsort(keys)
        rm_new = gather_entities(rm, perm)
        prep_new = permute_mask_bits(prepared_mask, perm)
        rm_bits_new = permute_mask_bits(msgs, perm)
        ctl_bits = msgs & jnp.uint32(0b11 << n)  # commit/abort: not per-RM
        return jnp.concatenate(
            [
                rm_new,
                states[:, n : n + 1],
                prep_new[:, None],
                (rm_bits_new | ctl_bits)[:, None],
            ],
            axis=1,
        ).astype(jnp.uint32)

    def decode(self, row):
        n = self.rm_count
        names = {0: "working", 1: "prepared", 2: "committed", 3: "aborted"}
        tm_names = {0: "init", 1: "committed", 2: "aborted"}
        msgs = int(row[n + 2])
        msg_set = {f"prepared({i})" for i in range(n) if msgs & (1 << i)}
        if msgs & (1 << n):
            msg_set.add("commit")
        if msgs & (1 << (n + 1)):
            msg_set.add("abort")
        return (
            tuple(names[int(x)] for x in row[:n]),
            tm_names[int(row[n])],
            int(row[n + 1]),
            frozenset(msg_set),
        )

    def action_label(self, row, action_index):
        if action_index == 0:
            return "tm_commit"
        if action_index == 1:
            return "tm_abort"
        i, kind = divmod(action_index - 2, 5)
        return (
            ["tm_rcv_prepared", "rm_prepare", "rm_choose_abort",
             "rm_rcv_commit", "rm_rcv_abort"][kind],
            i,
        )


# -- increment (shared-memory interleaving / data-race demo) -------------------


@dataclass
class TensorIncrement(TensorModel):
    """Lost-update race demo (ref: examples/increment.rs:108-202),
    tensor-encoded. Lanes: [i, t0, pc0, t1, pc1, ...]; one action slot per
    thread (each thread has at most one enabled step: read at pc=1, write at
    pc=2). Goldens with 2 threads: 13 states, 8 under symmetry
    (ref: examples/increment.rs:32-105).

    The "fin" property (ALWAYS sum(pc==3) == i) is violated by the race; an
    undiscoverable `sometimes` property forces full enumeration when needed,
    mirroring the host test strategy. Its `definition_key()` is its class
    and fields: `__post_init__` derives `lanes`, `max_actions` and
    `representative` from them.
    """

    thread_count: int
    symmetry: bool = False
    full_enumeration: bool = False  # add an unfindable sometimes property

    def __post_init__(self):
        self.lanes = 1 + 2 * self.thread_count
        self.max_actions = self.thread_count
        if self.symmetry:
            self.representative = self._representative

    def definition_key(self):
        return dataclass_definition_key(self, TensorIncrement)

    def init_states(self):
        row = [0] + [0, 1] * self.thread_count
        return jnp.asarray([row], dtype=jnp.uint32)

    def expand(self, states):
        i = states[:, 0]
        succ_list, valid_list = [], []
        for tid in range(self.thread_count):
            t = states[:, 1 + 2 * tid]
            pc = states[:, 2 + 2 * tid]
            is_read = pc == 1
            is_write = pc == 2
            # read: t <- i, pc <- 2;  write: i <- t + 1, pc <- 3.
            new_i = jnp.where(is_write, t + 1, i)
            new_t = jnp.where(is_read, i, t)
            new_pc = jnp.where(is_read, 2, jnp.where(is_write, 3, pc))
            cols = [new_i]
            for o in range(self.thread_count):
                if o == tid:
                    cols += [new_t, new_pc]
                else:
                    cols += [states[:, 1 + 2 * o], states[:, 2 + 2 * o]]
            succ_list.append(jnp.stack(cols, axis=1))
            valid_list.append(is_read | is_write)
        succs = jnp.stack(succ_list, axis=1).astype(jnp.uint32)
        valid = jnp.stack(valid_list, axis=1)
        return succs, valid

    def _representative(self, states):
        """Sort per-thread (t, pc) pairs — the device analogue of the host
        IncrementState.representative (13 → 8 at 2 threads)."""
        from .symmetry import gather_entities, stable_argsort

        n = self.thread_count
        t = states[:, 1::2]
        pc = states[:, 2::2]
        # Key order matches the host's sorted((t, pc)) tuples.
        perm = stable_argsort(t * jnp.uint32(8) + pc)
        t_new = gather_entities(t, perm)
        pc_new = gather_entities(pc, perm)
        out = [states[:, 0:1]]
        for k in range(n):
            out += [t_new[:, k : k + 1], pc_new[:, k : k + 1]]
        return jnp.concatenate(out, axis=1).astype(jnp.uint32)

    def properties(self):
        n = self.thread_count

        def fin(model, states):
            done = jnp.stack(
                [states[:, 2 + 2 * t] == 3 for t in range(n)], axis=1
            ).sum(axis=1)
            return done == states[:, 0]

        props = [TensorProperty.always("fin", fin)]
        if self.full_enumeration:
            props.append(
                TensorProperty.sometimes(
                    "unreachable",
                    lambda m, s: jnp.zeros(s.shape[0], dtype=bool),
                )
            )
        return props

    def decode(self, row):
        n = self.thread_count
        return (
            int(row[0]),
            tuple((int(row[1 + 2 * t]), int(row[2 + 2 * t])) for t in range(n)),
        )

    def action_label(self, row, action_index):
        pc = int(row[2 + 2 * action_index])
        return ("read" if pc == 1 else "write", action_index)


@dataclass
class TensorIncrementLock(TensorModel):
    """Lock-fixed increment (ref: examples/increment_lock.rs), tensor-encoded.
    Lanes: [i, lock, t0, pc0, t1, pc1, ...]; one action slot per thread (each
    thread has at most one enabled step: lock at pc=0, read at pc=1, write at
    pc=2, release at pc=3).

    Device symmetry sorts the per-thread (t, pc) pairs — identical to the
    host representative (``tuple(sorted(s))``), and since that pair IS the
    entire per-entity state there are no satellite-bit ties to split: the
    reduced counts match the host ``check-sym`` goldens exactly (contrast the
    2PC case in tensor/symmetry.py's COUNT CONTRACT). Its `definition_key()`
    is its class and fields: `__post_init__` derives `lanes`, `max_actions`
    and `representative` from them."""

    thread_count: int
    symmetry: bool = False

    def __post_init__(self):
        self.lanes = 2 + 2 * self.thread_count
        self.max_actions = self.thread_count
        if self.symmetry:
            self.representative = self._representative

    def definition_key(self):
        return dataclass_definition_key(self, TensorIncrementLock)

    def init_states(self):
        return jnp.asarray(
            [[0, 0] + [0, 0] * self.thread_count], dtype=jnp.uint32
        )

    def expand(self, states):
        i = states[:, 0]
        lock = states[:, 1]
        succ_list, valid_list = [], []
        for tid in range(self.thread_count):
            t = states[:, 2 + 2 * tid]
            pc = states[:, 3 + 2 * tid]
            can_lock = (pc == 0) & (lock == 0)
            is_read = pc == 1
            is_write = pc == 2
            can_rel = (pc == 3) & (lock == 1)
            new_i = jnp.where(is_write, t + 1, i)
            new_lock = jnp.where(
                can_lock, 1, jnp.where(can_rel, 0, lock)
            ).astype(jnp.uint32)
            new_t = jnp.where(is_read, i, t)
            new_pc = jnp.where(
                can_lock,
                1,
                jnp.where(
                    is_read, 2, jnp.where(is_write, 3, jnp.where(can_rel, 4, pc))
                ),
            ).astype(jnp.uint32)
            cols = [new_i, new_lock]
            for o in range(self.thread_count):
                if o == tid:
                    cols += [new_t, new_pc]
                else:
                    cols += [states[:, 2 + 2 * o], states[:, 3 + 2 * o]]
            succ_list.append(jnp.stack(cols, axis=1))
            valid_list.append(can_lock | is_read | is_write | can_rel)
        succs = jnp.stack(succ_list, axis=1).astype(jnp.uint32)
        valid = jnp.stack(valid_list, axis=1)
        return succs, valid

    def _representative(self, states):
        from .symmetry import gather_entities, stable_argsort

        t = states[:, 2::2]
        pc = states[:, 3::2]
        # Key order matches the host's sorted((t, pc)) tuples (t <= threads,
        # pc <= 4, so t*8+pc is collision-free and order-preserving).
        perm = stable_argsort(t * jnp.uint32(8) + pc)
        t_new = gather_entities(t, perm)
        pc_new = gather_entities(pc, perm)
        out = [states[:, 0:1], states[:, 1:2]]
        for k in range(self.thread_count):
            out += [t_new[:, k : k + 1], pc_new[:, k : k + 1]]
        return jnp.concatenate(out, axis=1).astype(jnp.uint32)

    def properties(self):
        n = self.thread_count

        def fin(model, states):
            done = jnp.stack(
                [states[:, 3 + 2 * t] >= 3 for t in range(n)], axis=1
            ).sum(axis=1)
            return done == states[:, 0]

        def mutex(model, states):
            held = jnp.stack(
                [
                    (states[:, 3 + 2 * t] >= 1) & (states[:, 3 + 2 * t] < 4)
                    for t in range(n)
                ],
                axis=1,
            ).sum(axis=1)
            return held <= 1

        return [
            TensorProperty.always("fin", fin),
            TensorProperty.always("mutex", mutex),
        ]

    def decode(self, row):
        n = self.thread_count
        return (
            int(row[0]),
            bool(row[1]),
            tuple((int(row[2 + 2 * t]), int(row[3 + 2 * t])) for t in range(n)),
        )

    def action_label(self, row, action_index):
        pc = int(row[3 + 2 * action_index])
        return (
            {0: "lock", 1: "read", 2: "write", 3: "release"}.get(pc, "?"),
            action_index,
        )


# -- Raft leader election ------------------------------------------------------

# Server roles (one lane each).
_FOLLOWER, _CANDIDATE, _LEADER = 0, 1, 2


@dataclass
class TensorRaft(TensorModel):
    """Raft leader election (Ongaro & Ousterhout §5.2), tensor-encoded — the
    model-zoo workload built FOR the device simulation engine: terms are
    bounded by `max_term`, so the space is finite but grows so fast with
    `server_count`/`max_term` that the exhaustive engines only finish the
    small configs (the goldens), while random walks cover the large ones.

    Lanes (grouped): [term[0..n], role[0..n], voted[0..n]] — per server its
    current term, role (follower/candidate/leader), and vote in its current
    term (0 = none, k+1 = server k). Message passing is collapsed into
    direct peer-state actions (the classic shared-memory reduction of the
    election protocol — votes are granted only for a strictly newer term,
    so each server votes at most once per term and two leaders can never
    share a term).

    Actions (static slots):
      [0, n)            timeout(i):  non-leader i starts an election —
                        term+1, candidate, votes for itself
      [n, 2n)           win(i):      candidate i with a strict majority of
                        same-term votes becomes leader
      [2n, 2n + n(n-1)) vote(i<-j):  j grants its vote to candidate i
                        (only when term_j < term_i; j adopts the term)
      [.., + n(n-1))    beat(i->j):  leader i brings j to its term (j
                        follows, vote cleared — it never voted in that
                        term)

    Properties: "election safety" (ALWAYS — no two leaders share a term),
    "leader elected" (EVENTUALLY — split-vote walks that exhaust max_term
    without a leader are genuine counterexamples: Raft's liveness needs
    randomized timeouts the adversarial scheduler doesn't grant), and
    "can elect" (SOMETIMES — the positive witness). Its `definition_key()`
    is its class and fields: `__post_init__` derives `lanes` and
    `max_actions` from them."""

    server_count: int = 3
    max_term: int = 3

    def __post_init__(self):
        n = self.server_count
        self.lanes = 3 * n
        self.max_actions = 2 * n + 2 * n * (n - 1)

    def definition_key(self):
        return dataclass_definition_key(self, TensorRaft)

    def init_states(self):
        return jnp.zeros((1, self.lanes), dtype=jnp.uint32)

    def _split(self, states):
        n = self.server_count
        return states[:, :n], states[:, n : 2 * n], states[:, 2 * n :]

    def expand(self, states):
        n = self.server_count
        terms, roles, voted = self._split(states)
        succs, valids = [], []

        def build(t, r, v, valid):
            succs.append(jnp.concatenate([t, r, v], axis=1))
            valids.append(valid)

        for i in range(n):
            valid = (roles[:, i] != _LEADER) & (
                terms[:, i] < jnp.uint32(self.max_term)
            )
            build(
                terms.at[:, i].set(terms[:, i] + 1),
                roles.at[:, i].set(_CANDIDATE),
                voted.at[:, i].set(i + 1),
                valid,
            )
        for i in range(n):
            votes = (
                (terms == terms[:, i : i + 1]) & (voted == jnp.uint32(i + 1))
            ).sum(axis=1)
            valid = (roles[:, i] == _CANDIDATE) & (votes * 2 > n)
            build(terms, roles.at[:, i].set(_LEADER), voted, valid)
        for i in range(n):
            for j in range(n):
                if i == j:
                    continue
                valid = (roles[:, i] == _CANDIDATE) & (
                    terms[:, j] < terms[:, i]
                )
                build(
                    terms.at[:, j].set(terms[:, i]),
                    roles.at[:, j].set(_FOLLOWER),
                    voted.at[:, j].set(i + 1),
                    valid,
                )
        for i in range(n):
            for j in range(n):
                if i == j:
                    continue
                valid = (roles[:, i] == _LEADER) & (
                    terms[:, j] < terms[:, i]
                )
                build(
                    terms.at[:, j].set(terms[:, i]),
                    roles.at[:, j].set(_FOLLOWER),
                    voted.at[:, j].set(0),
                    valid,
                )
        return (
            jnp.stack(succs, axis=1).astype(jnp.uint32),
            jnp.stack(valids, axis=1),
        )

    def properties(self):
        n = self.server_count

        def safety(model, states):
            terms, roles, _v = model._split(states)
            bad = jnp.zeros(states.shape[0], dtype=bool)
            for i in range(n):
                for j in range(i + 1, n):
                    bad = bad | (
                        (roles[:, i] == _LEADER)
                        & (roles[:, j] == _LEADER)
                        & (terms[:, i] == terms[:, j])
                    )
            return ~bad

        def has_leader(model, states):
            _t, roles, _v = model._split(states)
            return (roles == _LEADER).any(axis=1)

        return [
            TensorProperty.always("election safety", safety),
            TensorProperty.eventually("leader elected", has_leader),
            TensorProperty.sometimes("can elect", has_leader),
        ]

    def decode(self, row):
        n = self.server_count
        role = {_FOLLOWER: "F", _CANDIDATE: "C", _LEADER: "L"}
        return tuple(
            (int(row[i]), role[int(row[n + i])], int(row[2 * n + i]) - 1)
            for i in range(n)
        )

    def action_label(self, row, action_index):
        n = self.server_count
        a = action_index
        if a < n:
            return f"timeout({a})"
        if a < 2 * n:
            return f"win({a - n})"
        a -= 2 * n
        pairs = [(i, j) for i in range(n) for j in range(n) if i != j]
        if a < n * (n - 1):
            i, j = pairs[a]
            return f"vote({i}<-{j})"
        i, j = pairs[a - n * (n - 1)]
        return f"beat({i}->{j})"
