"""Tensor-encoded ABD linearizable register: a hand-built device encoding of
the actor system in `stateright_tpu.examples.abd` (itself a port of
stateright's examples/linearizable-register.rs):
RegisterServer(AbdActor) x S plus RegisterClient(put_count=1) x C over an
unordered non-duplicating network, with the LinearizabilityTester history
and the properties evaluated ON DEVICE as vectorized masks. The reference's
bench.sh runs `linearizable-register check 2`: 2 clients, 3 servers.

There is no leader: every server that takes a client request runs its own
two-phase quorum operation (phase 1 queries the peers for the largest
(logical clock, server id) sequencer; phase 2 records the chosen sequencer
and value at the peers, a read writing back what it read), so the clients'
operations run at once at different servers.

Encoding decisions (every bound follows from the protocol):

- Operations. Client k's Put goes to server (S+k) % S with request id S+k
  and writes 'A'+k; its Get goes to server (S+k+1) % S with request id
  2(S+k) (ref: src/actor/register.rs:145-237). Op o = 2k (Put) or 2k+1
  (Get) names the request, its server, its requester and its write value,
  so a server's phase stores o alone. `__post_init__` checks that no server
  serves two ops of one request id, so o names an envelope's request
  exactly.
- Sequencers (clock, id) are codes clock*S + id, ordered as the host orders
  the tuples. Every write sets its clock to the largest it saw plus one, so
  clocks stay <= C (one write per client): (C+1)*S codes. Values are codes
  0 (the register's NULL) and 1+k ('A'+k): C+1 codes.
- Quorum. S <= 3, so a majority is 2: the server's own entry plus the
  first peer reply. Phase 1 therefore holds only its own (sequencer,
  value), captured when the request arrived (`cseq`, `cval`; a later
  Record may raise the server's own), and completes at its first AckQuery;
  phase 2 holds only its own ack and completes at its first AckRecord.
  Later replies to a finished phase are no-ops, elided as on the host
  (ref: src/actor/model.rs:345-347), and stay in flight. In phase 2 `cval`
  holds what a read returns (0 for a write) and `cseq` is 0.
- Each server packs into one lane: seq | val | phase (0 idle, 1, 2) | op |
  cseq | cval. The clients share one lane, 8 bits each, and the
  linearizability property is a mask over it: `tensor/register.py`'s
  `RegisterClients`, which TensorPaxos uses too.
- The network multiset is a sorted pool of `pool_size` u32 lanes of
  envelope vocabulary ids (EMPTY = 0xFFFFFFFF), rebuilt by the rank sort of
  `tensor/poolops.py`; duplicate ids are masked so that each distinct
  envelope is one Deliver action. A delivery emits at most S-1 envelopes
  (a broadcast to the peers).
- `pool_size` 10: the most envelopes in flight that the whole 2-client,
  3-server space reaches (benchmark/reference/bfs_ref_abd.cpp, whose
  multiset is unbounded, prints it as `max_in_flight`; 2 clients and 2
  servers reach 2). A successor with more becomes the all-ones POISON
  row, which the "pool capacity" property reports as a discovery instead
  of dropping it.

Properties: "linearizable" (always), "value chosen" (sometimes: a GetOk of
a written value in flight), "pool capacity" (always).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp

from .model import TensorModel, TensorProperty, dataclass_definition_key
from .poolops import rank_sort
from .register import PH_GET_INFLIGHT, PH_PUT_INFLIGHT, RegisterClients

EMPTY = np.uint32(0xFFFFFFFF)

# Envelope types, in vocabulary order.
PUT, GET, PUTOK, GETOK, QUERY, ACKQ, RECORD, ACKR = range(8)
TYPE_NAMES = ("Put", "Get", "PutOk", "GetOk", "Query", "AckQuery", "Record",
              "AckRecord")
NULL_VALUE = "\x00"
# A completed Get that read NULL: no combo of the linearizability tables
# matches it (they never serialize a completed Get before every write). ABD
# never returns one: a client reads only after its own write reached a
# majority, which every read quorum meets.
RET_NULL = 3


def _bits(n_values: int) -> int:
    return max(int(n_values - 1).bit_length(), 1)


@dataclass
class TensorAbd(TensorModel):
    """Device ABD over C clients / S servers (the reference's `check 2` is
    the default).

    Its `definition_key()` is its class and fields: `__post_init__` derives
    the layout, `lanes`, `max_actions` and every vocabulary and
    linearizability table from them."""

    client_count: int = 2
    server_count: int = 3
    pool_size: int = 10

    def __post_init__(self):
        C, S = self.client_count, self.server_count
        if S not in (2, 3):
            # A quorum of 2 (own entry + first peer reply) and at most two
            # broadcast emissions are laid out for 2 or 3 servers.
            raise ValueError("TensorAbd supports server_count 2 or 3")
        self.clients = RegisterClients(C)  # raises above 3 clients
        self.n_ops = 2 * C
        self.op_server = [(S + o // 2 + o % 2) % S for o in range(self.n_ops)]
        self.op_request = [(1 + o % 2) * (S + o // 2) for o in range(self.n_ops)]
        served = list(zip(self.op_server, self.op_request))
        if len(set(served)) != len(served):
            raise ValueError("two ops share a server and a request id")
        self.NSEQ = (C + 1) * S
        self.NVAL = C + 1
        bs, bv, bo = _bits(self.NSEQ), _bits(self.NVAL), _bits(self.n_ops)
        # Server lane: seq | val | phase(2) | op | cseq | cval
        self.off_val = bs
        self.off_ph = bs + bv
        self.off_op = self.off_ph + 2
        self.off_cseq = self.off_op + bo
        self.off_cval = self.off_cseq + bs
        self.seq_mask, self.val_mask, self.op_mask = (
            (1 << bs) - 1, (1 << bv) - 1, (1 << bo) - 1)
        assert self.off_cval + bv <= 32

        self.client_lane = S
        self.pool_off = S + 1
        self.lanes = self.pool_off + self.pool_size
        self.max_actions = self.pool_size
        self.n_emit = S - 1
        self._build_vocab()

    def definition_key(self):
        return dataclass_definition_key(self, TensorAbd)

    def _build_vocab(self):
        """Envelope vocabulary: contiguous id ranges per message type (ref
        message set: examples/linearizable-register.rs:27-34 +
        src/actor/register.rs:17-31). A pair index p = o*(S-1) + d names op
        o's exchange with the d-th peer of its server."""
        C, S = self.client_count, self.server_count
        NP = self.n_ops * (S - 1)
        self.PUT0 = 0  # Put(S+k, 'A'+k): client k -> op 2k's server
        self.GET0 = self.PUT0 + C  # Get(2(S+k)): client k -> op 2k+1's server
        self.PUTOK0 = self.GET0 + C  # PutOk: op 2k's server -> client k
        self.GETOK0 = self.PUTOK0 + C  # GetOk(value v): -> client k, k*NVAL+v
        self.QUERY0 = self.GETOK0 + C * self.NVAL  # server(o) -> peer, by p
        self.ACKQ0 = self.QUERY0 + NP  # peer -> server(o): (p*NSEQ+seq)*NVAL+val
        self.RECORD0 = self.ACKQ0 + NP * self.NSEQ * self.NVAL  # server(o) -> peer
        self.ACKR0 = self.RECORD0 + NP * self.NSEQ * self.NVAL  # peer -> server(o)
        self.V = self.ACKR0 + NP

        cols = ("typ", "op", "pair", "dst", "src", "seq", "val")
        T = {c: np.zeros(self.V, np.uint32) for c in cols}

        def put(i, **kw):
            for c, v in kw.items():
                T[c][i] = v

        for k in range(C):
            pw, gr = self.op_server[2 * k], self.op_server[2 * k + 1]
            put(self.PUT0 + k, typ=PUT, op=2 * k, dst=pw, src=S + k)
            put(self.GET0 + k, typ=GET, op=2 * k + 1, dst=gr, src=S + k)
            put(self.PUTOK0 + k, typ=PUTOK, op=2 * k, dst=k, src=pw)
            for v in range(self.NVAL):
                put(self.GETOK0 + k * self.NVAL + v, typ=GETOK, op=2 * k + 1,
                    dst=k, src=gr, val=v)
        for o in range(self.n_ops):
            own = self.op_server[o]
            for d in range(S - 1):
                p = o * (S - 1) + d
                peer = d + (d >= own)  # the d-th peer, in id order
                put(self.QUERY0 + p, typ=QUERY, op=o, pair=p, dst=peer, src=own)
                put(self.ACKR0 + p, typ=ACKR, op=o, pair=p, dst=own, src=peer)
                for sq in range(self.NSEQ):
                    for v in range(self.NVAL):
                        j = (p * self.NSEQ + sq) * self.NVAL + v
                        put(self.ACKQ0 + j, typ=ACKQ, op=o, pair=p, dst=own,
                            src=peer, seq=sq, val=v)
                        put(self.RECORD0 + j, typ=RECORD, op=o, pair=p,
                            dst=peer, src=own, seq=sq, val=v)
        self._T = T

        # All decode fields in ONE u32 per envelope id: the expand pays a
        # single [B, M] table gather.
        packed = np.zeros(self.V, np.uint32)
        off = 0
        self._field_off = {}
        for c in ("typ", "op", "pair", "dst", "seq", "val"):
            w = _bits(int(T[c].max()) + 1)
            self._field_off[c] = (off, (1 << w) - 1)
            packed |= T[c] << np.uint32(off)
            off += w
        assert off <= 32
        self._PACKED = packed

    # -- field helpers (all shapes broadcast) ----------------------------------

    def _srv_unpack(self, lane):
        u = jnp.uint32
        return (
            lane & u(self.seq_mask),
            (lane >> u(self.off_val)) & u(self.val_mask),
            (lane >> u(self.off_ph)) & u(3),
            (lane >> u(self.off_op)) & u(self.op_mask),
            (lane >> u(self.off_cseq)) & u(self.seq_mask),
            (lane >> u(self.off_cval)) & u(self.val_mask),
        )

    def _srv_pack(self, seq, val, ph, op, cseq, cval):
        u = jnp.uint32
        return (
            seq.astype(u)
            | (val.astype(u) << u(self.off_val))
            | (u(ph) << u(self.off_ph))
            | (op.astype(u) << u(self.off_op))
            | (cseq.astype(u) << u(self.off_cseq))
            | (cval.astype(u) << u(self.off_cval))
        )

    # -- TensorModel interface -------------------------------------------------

    def init_states(self):
        C, S = self.client_count, self.server_count
        row = np.zeros(self.lanes, np.uint32)
        row[:S] = np.arange(S)  # seq (0, Id(s)), value NULL, idle
        row[self.pool_off:] = [self.PUT0 + k for k in range(C)] + [
            int(EMPTY)] * (self.pool_size - C)
        return jnp.asarray(row[None, :])

    def expand(self, states):
        S, M = self.server_count, self.pool_size
        B = states.shape[0]
        u = jnp.uint32
        with jax.named_scope("deliver"):
            pool = states[:, self.pool_off:]  # [B, M]
            clients = states[:, self.client_lane]  # [B]
            idx = jnp.minimum(pool, u(self.V - 1)).astype(jnp.int32)
            packed = jnp.take(jnp.asarray(self._PACKED), idx)

            def field(name):
                off, mask = self._field_off[name]
                return (packed >> u(off)) & u(mask)

            typ, op, pair, dst = field("typ"), field("op"), field("pair"), field("dst")
            mseq, mval = field("seq"), field("val")
            # One Deliver action per DISTINCT in-flight envelope; the pool is
            # sorted, so duplicates are adjacent.
            first = jnp.concatenate(
                [jnp.ones((B, 1), bool), pool[:, 1:] != pool[:, :-1]], axis=1)
            deliverable = (pool != EMPTY) & first
            is_server_msg = (typ != PUTOK) & (typ != GETOK)
            # The target server's lane per action slot, as a one-hot select.
            srv_all = states[:, :S]  # [B, S]
            d_srv = jnp.where(is_server_msg, dst, 0).astype(jnp.int32)
            sL = jnp.zeros((B, M), u)
            for s in range(S):
                sL = jnp.where(d_srv == s, srv_all[:, s:s + 1], sL)
            seq, val, ph, sop, cseq, cval = self._srv_unpack(sL)
            csh, cfield, cphase = self.clients.field(clients, dst, is_server_msg)

        with jax.named_scope("server"):
            nL, ncf = sL, cfield
            ems = [jnp.full((B, M), EMPTY) for _ in range(self.n_emit)]
            ok = jnp.zeros((B, M), bool)
            zero = jnp.zeros((B, M), u)
            is_write = (op & u(1)) == 0
            k = op >> u(1)
            pbase = op * u(S - 1)  # pair index of the op's first peer

            def emit(g, ids):
                for i, e in enumerate(ids):
                    ems[i] = jnp.where(g, e, ems[i])

            # (AbdActor.on_msg in examples/abd.py, branch by branch.)
            # Put / Get at an idle server: query the peers.
            g = (typ <= GET) & (ph == 0)
            nL = jnp.where(g, self._srv_pack(seq, val, 1, op, seq, val), nL)
            emit(g, [u(self.QUERY0) + pbase + u(d) for d in range(S - 1)])
            ok = ok | g

            # Query: answer with the own sequencer and value.
            g = typ == QUERY
            emit(g, [u(self.ACKQ0) + (pair * u(self.NSEQ) + seq) * u(self.NVAL) + val])
            ok = ok | g

            # AckQuery of the phase-1 op: the quorum is reached; the larger
            # sequencer wins, a write bumps its clock, and the chosen pair
            # is recorded at the peers and at this server.
            g = (typ == ACKQ) & (ph == 1) & (sop == op)
            take = mseq > cseq
            bseq = jnp.where(take, mseq, cseq)
            bval = jnp.where(take, mval, cval)
            wseq = (bseq // u(S) + u(1)) * u(S) + dst
            nseq = jnp.where(is_write, wseq, bseq)
            nval = jnp.where(is_write, k + u(1), bval)
            read = jnp.where(is_write, u(0), bval)
            up = nseq > seq
            nL = jnp.where(g, self._srv_pack(
                jnp.where(up, nseq, seq), jnp.where(up, nval, val), 2, op,
                zero, read), nL)
            emit(g, [u(self.RECORD0) + ((pbase + u(d)) * u(self.NSEQ) + nseq)
                     * u(self.NVAL) + nval for d in range(S - 1)])
            ok = ok | g

            # Record: ack it; adopt a larger sequencer.
            g = typ == RECORD
            up = g & (mseq > seq)
            nL = jnp.where(up, self._srv_pack(mseq, mval, 0, zero, zero, zero)
                           | (sL & ~u(self.seq_mask | (self.val_mask << self.off_val))),
                           nL)
            emit(g, [u(self.ACKR0) + pair])
            ok = ok | g

            # AckRecord of the phase-2 op: the quorum is reached; reply to
            # the client and go idle.
            g = (typ == ACKR) & (ph == 2) & (sop == op)
            reply = jnp.where(is_write, u(self.PUTOK0) + k,
                              u(self.GETOK0) + k * u(self.NVAL) + cval)
            nL = jnp.where(g, self._srv_pack(seq, val, 0, zero, zero, zero), nL)
            emit(g, [reply])
            ok = ok | g

            # PutOk: the client Gets next; GetOk: the client is done.
            g = (typ == PUTOK) & (cphase == PH_PUT_INFLIGHT)
            ncf = self.clients.put_ok(g, clients, dst, ncf)
            emit(g, [u(self.GET0) + dst])
            ok = ok | g
            g = (typ == GETOK) & (cphase == PH_GET_INFLIGHT)
            ret = jnp.where(mval == 0, u(RET_NULL), mval - u(1))
            ncf = self.clients.get_ok(g, cfield, ret, ncf)
            ok = ok | g

            valid = deliverable & ok
            srv_sel = (jnp.arange(S)[None, None, :] == d_srv[:, :, None]) & (
                is_server_msg[:, :, None])  # [B, M, S]
            new_srv = jnp.where(srv_sel, nL[:, :, None], srv_all[:, None, :])
            ncl = self.clients.lane(clients, ncf, csh, is_server_msg)

        with jax.named_scope("emit"):
            # Drop the delivered slot, add the emissions, and restore the
            # sorted-multiset form; past `pool_size` the row is POISON.
            act = jnp.arange(M, dtype=jnp.uint32)[None, :]
            parts = [jnp.where(act == i, EMPTY, pool[:, i:i + 1])
                     for i in range(M)] + ems
            npool, overflow = rank_sort(parts, M)
            succ = jnp.concatenate([new_srv, ncl[:, :, None], npool], axis=2)
            succ = jnp.where(overflow[:, :, None], jnp.uint32(EMPTY), succ)
        return succ, valid

    # -- properties ------------------------------------------------------------

    def properties(self):
        C = self.client_count

        def _is_poison(states):
            return jnp.all(states == jnp.uint32(EMPTY), axis=1)

        def linearizable(model, states):
            # Poison rows are reported by "pool capacity", not here.
            return model.clients.linearizable(
                states[:, model.client_lane]) | _is_poison(states)

        def value_chosen(model, states):
            pool = states[:, model.pool_off:]
            rel = pool - jnp.uint32(model.GETOK0)  # wraps below the range
            return jnp.any((rel < C * model.NVAL) & (rel % model.NVAL != 0),
                           axis=1)

        def pool_capacity(model, states):
            return ~_is_poison(states)

        return [
            TensorProperty.always("linearizable", linearizable),
            TensorProperty.sometimes("value chosen", value_chosen),
            TensorProperty.always("pool capacity", pool_capacity),
        ]

    def trace_args(self) -> dict:
        return dict(super().trace_args(), vocabulary=self.V,
                    lin_rows=len(self.clients.lin_phase),
                    pool_size=self.pool_size)

    # -- display ---------------------------------------------------------------

    def _value(self, code: int):
        return NULL_VALUE if code == 0 else chr(ord("A") + code - 1)

    def _seq(self, code: int):
        return divmod(code, self.server_count)

    def decode(self, row):
        """A state as the host model names its parts: per server its
        sequencer (clock, id), value and phase number, and for a phase its
        request id, requester actor id and write value (phase 1: the
        responses as (server, sequencer, value)) or read value and acks
        (phase 2); per client phase, ret and frontier
        (`RegisterClients.decode`); the envelopes in flight as (type, src
        actor, dst actor, request id, sequencer or None, value or None)."""
        S = self.server_count
        row = [int(x) for x in row]
        servers = []
        for s in range(S):
            lane = row[s]
            seq, val, ph, o, cseq, cval = [(lane >> off) & mask for off, mask in (
                (0, self.seq_mask), (self.off_val, self.val_mask),
                (self.off_ph, 3), (self.off_op, self.op_mask),
                (self.off_cseq, self.seq_mask), (self.off_cval, self.val_mask))]
            srv = dict(seq=self._seq(seq), val=self._value(val), phase=ph)
            if ph == 1:
                srv.update(request_id=self.op_request[o], requester=S + o // 2,
                           write=None if o % 2 else self._value(o // 2 + 1),
                           responses=[(s, self._seq(cseq), self._value(cval))])
            elif ph == 2:
                srv.update(request_id=self.op_request[o], requester=S + o // 2,
                           read=self._value(cval) if o % 2 else None, acks=[s])
            servers.append(srv)
        network = [self._envelope(e) for e in row[self.pool_off:] if e != int(EMPTY)]
        return dict(servers=servers,
                    clients=self.clients.decode(row[self.client_lane]),
                    network=network)

    def _envelope(self, e: int) -> tuple:
        T = self._T
        typ, o = int(T["typ"][e]), int(T["op"][e])
        src, dst = int(T["src"][e]), int(T["dst"][e])
        if typ in (PUTOK, GETOK):
            dst += self.server_count  # client index -> actor id
        seq = self._seq(int(T["seq"][e])) if typ in (ACKQ, RECORD) else None
        val = (self._value(int(T["val"][e])) if typ in (ACKQ, RECORD, GETOK)
               else self._value(o // 2 + 1) if typ == PUT else None)
        return (TYPE_NAMES[typ], src, dst, self.op_request[o], seq, val)

    def action_label(self, row, action_index):
        e = int(row[self.pool_off + action_index])
        if e == int(EMPTY):
            return "noop"
        name, src, dst, req, seq, val = self._envelope(e)
        args = ", ".join(repr(x) for x in (req, seq, val) if x is not None)
        return f"Deliver({src}->{dst}, {name}({args}))"
