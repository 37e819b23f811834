"""The register clients and the on-device linearizability mask, shared by
the device encodings of register protocols (`tensor/paxos.py` TensorPaxos,
`tensor/abd.py` TensorAbd).

Both encode the same clients: `RegisterClient(put_count=1)` x C (ref:
src/actor/register.rs:145-237), client k Putting 'A'+k to one server and
then Getting from the next, with the `LinearizabilityTester` over a
register as the model's history. Each client packs into 8 bits of one
shared u32 lane:

- bits 0-1: phase (PH_PUT_INFLIGHT, PH_GET_INFLIGHT, PH_DONE; the host
  client never rests between its PutOk and its Get send, so only three
  phases exist);
- bits 2-3: the value its Get returned, as the writing client's index k
  (the register holds 'A'+k);
- bits 4..: the real-time frontier its Get captured: per peer client, in
  increasing client order, the peer's completed ops (0-2, 2 bits each) at
  the Get's invocation (ref: src/semantics/linearizability.rs:102-129).

That is everything the tester's state adds to the checker state for this
workload, so C <= 3 (2 + 2 + 2(C-1) <= 8 bits).

The linearizability property enumerates, at build time, every interleaving
of the <= 2C client ops that respects per-thread order (puts are mandatory
once completed, in-flight ops optional — ref:
src/semantics/linearizability.rs:193-280), compiles each to constant
constraint tables, and evaluates ALL of them branchlessly per state batch:
an exhaustive linearizability check as a TPU mask.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

PH_PUT_INFLIGHT, PH_GET_INFLIGHT, PH_DONE = 0, 1, 2


def build_lin_tables(client_count: int):
    """Static interleaving enumeration for the on-device linearizability
    mask: `(phase [NC, C], ret [NC, C], maxf [NC, C, C])`. Each combo =
    (which ops are included, in which order); compiled to: allowed-phase
    bitmask per client, expected Get return per client (-1: no Get /
    unconstrained), and the max real-time frontier each included Get
    tolerates toward each peer."""
    C = client_count
    NULL = -2  # register holds no client value yet

    combos_phase, combos_ret, combos_maxf = [], [], []

    def orders(included):
        """All interleavings of the included ops (tuples of (client,
        'p'|'g')) that keep each client's put before its get."""
        ops = []
        for c, pat in enumerate(included):
            if pat >= 1:
                ops.append((c, "p"))
            if pat == 2:
                ops.append((c, "g"))
        seqs = [[]]
        for _ in range(len(ops)):
            nxt = []
            for seq in seqs:
                used = set(seq)
                for op in ops:
                    if op in used:
                        continue
                    if op[1] == "g" and (op[0], "p") not in used:
                        continue
                    nxt.append(seq + [op])
            seqs = nxt
        return seqs or [[]]

    def gen(prefix):
        if len(prefix) == C:
            for seq in orders(prefix):
                # Phase constraints per client: pattern 0 (put excluded)
                # requires phase==PUT_INFLIGHT; pattern 1 (put only)
                # requires the get not completed; pattern 2 allows any
                # phase with the get in existence.
                pm, ret, maxf = [], [], []
                for c, pat in enumerate(prefix):
                    if pat == 0:
                        pm.append(1 << PH_PUT_INFLIGHT)
                    elif pat == 1:
                        pm.append((1 << PH_PUT_INFLIGHT) | (1 << PH_GET_INFLIGHT))
                    else:
                        pm.append((1 << PH_GET_INFLIGHT) | (1 << PH_DONE))
                # Replay the register through the sequence; expected value
                # of each included get is static.
                val = NULL
                expected = {c: None for c in range(C)}
                for c, kind in seq:
                    if kind == "p":
                        val = c
                    else:
                        expected[c] = val
                for c, pat in enumerate(prefix):
                    if pat == 2:
                        e = expected[c]
                        ret.append(-1 if e == NULL else e)
                    else:
                        ret.append(-1 if pat < 2 else 0)
                # -1 ret with pattern 2 means: only an in-flight get can
                # satisfy this combo (a completed get returned a real
                # value, but the combo serializes it before any write).
                mf = [[2] * C for _ in range(C)]
                for c, pat in enumerate(prefix):
                    if pat != 2:
                        continue
                    gpos = seq.index((c, "g"))
                    for c2 in range(C):
                        if c2 == c:
                            continue
                        before = set(seq[:gpos])
                        if (c2, "p") not in before:
                            mf[c][c2] = 0
                        elif (c2, "g") not in before:
                            mf[c][c2] = 1
                combos_phase.append(pm)
                combos_ret.append(ret)
                combos_maxf.append(mf)
            return
        for pat in (0, 1, 2):
            gen(prefix + [pat])

    gen([])
    phase = np.asarray(combos_phase, np.uint32)  # [NC, C]
    ret = np.asarray(combos_ret, np.int32)  # [NC, C]
    maxf = np.asarray(combos_maxf, np.uint32)  # [NC, C, C]
    # Distinct interleavings often compile to identical constraint rows
    # (e.g. two puts both overwritten before any included read); dedupe —
    # every row costs a [B, NC, C] mask evaluation in the hot loop.
    stacked = np.concatenate(
        [phase, ret.astype(np.int64), maxf.reshape(len(maxf), -1)], axis=1
    )
    _, keep = np.unique(stacked, axis=0, return_index=True)
    keep = np.sort(keep)
    return phase[keep], ret[keep], maxf[keep]


class RegisterClients:
    """C register clients in one u32 lane, their reactions to PutOk and
    GetOk, and the linearizability mask over that lane."""

    def __init__(self, client_count: int):
        if client_count > 3:
            raise ValueError("client field encoding supports client_count <= 3")
        self.client_count = client_count
        self.lin_phase, self.lin_ret, self.lin_maxf = build_lin_tables(
            client_count
        )

    def field(self, clients, dst, is_server_msg):
        """`(shift, field, phase)` of the client each action slot delivers
        to (`dst`, where the message is not a server's)."""
        csh = (jnp.where(is_server_msg, 0, dst) * 8).astype(jnp.uint32)
        cfield = (clients[:, None] >> csh) & jnp.uint32(0xFF)
        return csh, cfield, cfield & jnp.uint32(3)

    def put_ok(self, g, clients, dst, ncf):
        """The client field after a PutOk where `g`: on_return(Write) then
        on_invoke(Read) in one transition, the Read capturing the real-time
        frontier from the other clients' CURRENT completed-op counts (ref:
        src/actor/model.rs:348-357 ordering; linearizability.rs:102-129)."""
        u = jnp.uint32
        frontier = jnp.zeros(dst.shape, u)
        fshift = u(0)
        for c2 in range(self.client_count):
            # completed ops of client c2: 0 / 1 / 2 by phase
            f2 = (clients[:, None] >> u(8 * c2)) & u(3)
            comp = jnp.where(f2 == PH_DONE, u(2), jnp.where(f2 == PH_GET_INFLIGHT, u(1), u(0)))
            is_peer = dst != c2
            frontier = frontier | jnp.where(is_peer, comp << fshift, u(0))
            # peer slots are assigned in increasing client order, skipping self
            fshift = fshift + jnp.where(is_peer, u(2), u(0))
        return jnp.where(g, u(PH_GET_INFLIGHT) | (frontier << u(4)), ncf)

    def get_ok(self, g, cfield, ret, ncf):
        """The client field after a GetOk returning `ret` where `g`."""
        u = jnp.uint32
        return jnp.where(g, (cfield & ~u(3) & ~u(3 << 2)) | u(PH_DONE) | (ret << u(2)), ncf)

    def lane(self, clients, ncf, csh, is_server_msg):
        """The client lane of each successor: `ncf` written back into the
        delivered-to client's 8 bits (unchanged for server messages)."""
        u = jnp.uint32
        ncl = (
            clients[:, None] & ~(u(0xFF) << csh)
        ) | (ncf << csh)
        return jnp.where(is_server_msg, clients[:, None], ncl)

    def linearizable(self, clients):
        """bool[B]: some serialization of the clients' history is legal."""
        C = self.client_count
        with jax.named_scope("linearizable"):
            u = jnp.uint32
            phase = jnp.stack(
                [(clients >> u(8 * c)) & u(3) for c in range(C)], axis=1
            )  # [B, C]
            ret = jnp.stack(
                [(clients >> u(8 * c + 2)) & u(3) for c in range(C)], axis=1
            )
            frontier = jnp.stack(
                [
                    jnp.stack(
                        [
                            (
                                (clients >> u(8 * c + 4 + 2 * (c2 - (c2 > c))))
                                & u(3)
                                if c2 != c
                                else jnp.zeros_like(clients)
                            )
                            for c2 in range(C)
                        ],
                        axis=1,
                    )
                    for c in range(C)
                ],
                axis=1,
            )  # [B, C, C] — f of get_c toward peer c2 (0 when c2 == c)

            pm = jnp.asarray(self.lin_phase)  # [NC, C]
            exp = jnp.asarray(self.lin_ret)  # [NC, C]
            maxf = jnp.asarray(self.lin_maxf)  # [NC, C, C]

            ph = phase[:, None, :]  # [B, 1, C]
            phase_ok = ((pm[None] >> ph) & u(1)) == 1  # [B, NC, C]
            has_get = (pm[None] & u(1 << PH_DONE)) != 0
            ret_ok = (
                ~has_get
                | (ph == PH_GET_INFLIGHT)
                | ((exp[None] >= 0) & (ret[:, None, :] == exp[None].astype(u)))
            )
            # Completed gets in combos whose sequence reads NULL can never
            # match (GetOk always returns a real value): exp < 0 with a
            # completed get fails unless the get is merely in flight.
            rt_ok = jnp.all(
                frontier[:, None, :, :] <= maxf[None], axis=3
            )  # [B, NC, C]
            combo_ok = jnp.all(phase_ok & ret_ok & rt_ok, axis=2)  # [B, NC]
            return jnp.any(combo_ok, axis=1)

    def decode(self, lane: int) -> list:
        """The clients of one state's client lane, as dicts."""
        out = []
        for c in range(self.client_count):
            f = (lane >> (8 * c)) & 0xFF
            out.append(dict(phase=f & 3, ret=(f >> 2) & 3, frontier=f >> 4))
        return out
