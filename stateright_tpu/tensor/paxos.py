"""Tensor-encoded single-decree Paxos — the north-star device workload family
(BASELINE.json names the 3-client model; the encoding supports 1-3 clients,
and the validated golden config is 2 clients / 3 servers = 16,668 unique
states, ref: examples/paxos.rs:327,351).

This is a hand-built device encoding of the exact actor system in
`stateright_tpu.examples.paxos` (itself a port of examples/paxos.rs):
RegisterServer(PaxosActor) x S plus RegisterClient(put_count=1) x C over an
unordered non-duplicating network, with the LinearizabilityTester history and
both properties ("linearizable" always, "value chosen" sometimes) evaluated
ON DEVICE as vectorized masks.

Encoding decisions (all bounds are exact consequences of the protocol, see the
per-field comments):

- The network multiset is a sorted pool of `pool_size` u32 lanes holding
  envelope vocabulary ids (empty = 0xFFFFFFFF); sorting makes the multiset
  encoding canonical, and duplicate-id action slots are masked so the action
  enumeration matches the host's one-Deliver-per-distinct-envelope exactly.
- Each server packs into two lanes (ballot/proposal/accepted/decided/accepts
  and the per-peer `prepares` entries); each client packs into 8 bits of one
  shared lane (phase, read return value, and the real-time frontier its Get
  captured — everything the LinearizabilityTester state adds to the checker
  state for this workload).
- The linearizability property enumerates, at build time, every interleaving
  of the <= 2C client ops that respects per-thread order (puts are mandatory
  once completed, in-flight ops optional — ref:
  src/semantics/linearizability.rs:193-280), compiles each to constant
  constraint tables, and evaluates ALL of them branchlessly per state batch:
  an exhaustive linearizability check as a TPU mask.

Count parity with the host model was validated against the 16,668-state
golden (tests/test_tensor_paxos.py).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

import jax.numpy as jnp

from .model import TensorModel, TensorProperty, dataclass_definition_key
from .poolops import rank_sort

EMPTY = np.uint32(0xFFFFFFFF)

# Client phases (host RegisterClient with put_count=1 never rests between
# PutOk and the Get send, so only three phases exist).
PH_PUT_INFLIGHT, PH_GET_INFLIGHT, PH_DONE = 0, 1, 2


def _bits(n_values: int) -> int:
    return max(int(n_values - 1).bit_length(), 1)


@dataclass
class TensorPaxos(TensorModel):
    """Device Paxos over C clients / S servers (default matches the golden).

    Its `definition_key()` is its class and fields: `__post_init__` derives
    the layout, `lanes`, `max_actions` and every vocabulary and
    linearizability table from them."""

    client_count: int
    server_count: int = 3
    pool_size: int = 14

    # -- static layout ---------------------------------------------------------

    def __post_init__(self):
        C, S = self.client_count, self.server_count
        if S != 3:
            # Broadcast emission slots and quorum arithmetic are laid out for
            # the reference's 3-server configuration (em1/em2 = the two peers).
            raise ValueError("TensorPaxos currently supports server_count=3")
        if C > 3:
            # 2-bit proposal field and 8-bit client field (2 phase + 2 ret +
            # 2*(C-1) frontier bits) both cap C at 3.
            raise ValueError("client field encoding supports client_count <= 3")
        self.NB = 1 + C * S  # ballot codes: 0 = (0, Id(0)); 1+(r-1)*S+l
        self.NLA = 1 + C * S * C  # last_accepted codes: 0 = None; 1+(b-1)*C+k
        self.bb = _bits(self.NB)
        self.bla = _bits(self.NLA)
        self.bprep = 1 + self.bla  # per-peer prepares: present | la
        self.maj = S // 2 + 1

        # Server lane A: ballot | proposal(2b) | accepted(bla) | decided(1) |
        # accepts(S)
        self.off_prop = self.bb
        self.off_acc = self.bb + 2
        self.off_dec = self.off_acc + self.bla
        self.off_accs = self.off_dec + 1
        if self.off_accs + S > 32 or S * self.bprep > 32:
            raise ValueError("server fields exceed one u32 lane")

        # Lanes: [srvA, srvB] * S, clients, pool.
        self.client_lane = 2 * S
        self.pool_off = 2 * S + 1
        self.lanes = self.pool_off + self.pool_size
        self.max_actions = self.pool_size

        self._build_vocab()
        self._build_lin_tables()

    def definition_key(self):
        return dataclass_definition_key(self, TensorPaxos)

    def _build_vocab(self):
        """Envelope vocabulary: contiguous id ranges per message type
        (ref message set: examples/paxos.rs:66-89 + src/actor/register.rs:17-31).
        """
        C, S = self.client_count, self.server_count
        NBALLOT = C * S  # proposed ballots only (r >= 1)
        self.PUT0 = 0  # Put(S+k, 'A'+k) client k -> server (S+k)%S
        self.GET0 = self.PUT0 + C  # Get(2(S+k)) client k -> server (S+k+1)%S
        self.PUTOK0 = self.GET0 + C  # PutOk(S+k) server s -> client k
        self.GETOK0 = self.PUTOK0 + S * C  # GetOk(2(S+k), 'A'+v) -> client k
        self.PREPARE0 = self.GETOK0 + C * C  # Prepare(b) leader -> peer slot d
        self.PREPARED0 = self.PREPARE0 + NBALLOT * (S - 1)
        self.ACCEPT0 = self.PREPARED0 + NBALLOT * (S - 1) * self.NLA
        self.ACCEPTED0 = self.ACCEPT0 + NBALLOT * C * (S - 1)
        self.DECIDED0 = self.ACCEPTED0 + NBALLOT * (S - 1)
        self.V = self.DECIDED0 + NBALLOT * C * (S - 1)

        # Decode tables (numpy, gathered on device with jnp.take).
        TYP = np.zeros(self.V, np.uint32)  # 0..8 in id-range order
        DST = np.zeros(self.V, np.uint32)  # server index or client index
        BAL = np.zeros(self.V, np.uint32)  # ballot code (1-based; 0 n/a)
        PROP = np.zeros(self.V, np.uint32)  # proposal k
        LA = np.zeros(self.V, np.uint32)  # last_accepted code
        SRC = np.zeros(self.V, np.uint32)  # sender actor index
        VAL = np.zeros(self.V, np.uint32)  # GetOk value k

        def leader(b):
            return (b - 1) % S

        def peer(s, d):  # d-th peer of server s, in increasing id order
            return d + (d >= s)

        for k in range(C):
            i = self.PUT0 + k
            TYP[i], DST[i], PROP[i], SRC[i] = 0, (S + k) % S, k, S + k
            i = self.GET0 + k
            TYP[i], DST[i], PROP[i], SRC[i] = 1, (S + k + 1) % S, k, S + k
        for s in range(S):
            for k in range(C):
                i = self.PUTOK0 + s * C + k
                TYP[i], DST[i], PROP[i], SRC[i] = 2, k, k, s
        for k in range(C):
            for v in range(C):
                i = self.GETOK0 + k * C + v
                TYP[i], DST[i], PROP[i], VAL[i] = 3, k, k, v
                SRC[i] = (S + k + 1) % S
        for b in range(1, NBALLOT + 1):
            for d in range(S - 1):
                i = self.PREPARE0 + (b - 1) * (S - 1) + d
                TYP[i], DST[i], BAL[i], SRC[i] = 4, peer(leader(b), d), b, leader(b)
                for la in range(self.NLA):
                    j = self.PREPARED0 + ((b - 1) * (S - 1) + d) * self.NLA + la
                    TYP[j], DST[j], BAL[j], LA[j] = 5, leader(b), b, la
                    SRC[j] = peer(leader(b), d)
                i = self.ACCEPTED0 + (b - 1) * (S - 1) + d
                TYP[i], DST[i], BAL[i] = 7, leader(b), b
                SRC[i] = peer(leader(b), d)
                for k in range(C):
                    i = self.ACCEPT0 + ((b - 1) * C + k) * (S - 1) + d
                    TYP[i], DST[i], BAL[i], PROP[i] = 6, peer(leader(b), d), b, k
                    SRC[i] = leader(b)
                    i = self.DECIDED0 + ((b - 1) * C + k) * (S - 1) + d
                    TYP[i], DST[i], BAL[i], PROP[i] = 8, peer(leader(b), d), b, k
                    SRC[i] = leader(b)
        self._TYP, self._DST, self._BAL = TYP, DST, BAL
        self._PROP, self._LA, self._SRC, self._VAL = PROP, LA, SRC, VAL

        # Pack all seven decode fields into ONE u32 per envelope id: the
        # expand kernel then pays a single [B, M] table gather instead of
        # seven (TPU gathers cost per element — the 7-table form was the
        # bulk of the 5.8 ms/step expand fusion on v5e). Field widths are
        # exact for the supported C <= 3 / S == 3 configs (sum <= 23 bits).
        widths = [
            ("typ", 4, TYP),
            ("dst", _bits(max(S, C)), DST),
            ("bal", _bits(self.NB), BAL),
            ("prp", _bits(C), PROP),
            ("la", _bits(self.NLA), LA),
            ("src", _bits(S + C), SRC),
            ("val", _bits(C), VAL),
        ]
        assert sum(w for _, w, _t in widths) <= 32
        packed = np.zeros(self.V, np.uint32)
        off = 0
        self._field_off = {}
        for name, w, tbl in widths:
            assert int(tbl.max()) < (1 << w), (name, int(tbl.max()), w)
            self._field_off[name] = (off, (1 << w) - 1)
            packed |= tbl.astype(np.uint32) << np.uint32(off)
            off += w
        self._PACKED = packed

    def _build_lin_tables(self):
        """Static interleaving enumeration for the on-device linearizability
        mask. Each combo = (which ops are included, in which order); compiled
        to: allowed-phase bitmask per client, expected Get return per client
        (-1: no Get / unconstrained), and the max real-time frontier each
        included Get tolerates toward each peer."""
        C = self.client_count
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
        self._lin_phase = phase[keep]
        self._lin_ret = ret[keep]
        self._lin_maxf = maxf[keep]

    # -- field unpack helpers (all shapes broadcast) ---------------------------

    def _srv_unpack(self, laneA):
        m = jnp.uint32
        ballot = laneA & m((1 << self.bb) - 1)
        prop = (laneA >> m(self.off_prop)) & m(3)
        accepted = (laneA >> m(self.off_acc)) & m((1 << self.bla) - 1)
        decided = (laneA >> m(self.off_dec)) & m(1)
        accepts = (laneA >> m(self.off_accs)) & m((1 << self.server_count) - 1)
        return ballot, prop, accepted, decided, accepts

    def _srv_pack(self, ballot, prop, accepted, decided, accepts):
        m = jnp.uint32
        return (
            ballot.astype(jnp.uint32)
            | (prop.astype(jnp.uint32) << m(self.off_prop))
            | (accepted.astype(jnp.uint32) << m(self.off_acc))
            | (decided.astype(jnp.uint32) << m(self.off_dec))
            | (accepts.astype(jnp.uint32) << m(self.off_accs))
        )

    # -- TensorModel interface -------------------------------------------------

    def init_states(self):
        C = self.client_count
        row = np.zeros(self.lanes, np.uint32)
        pool = sorted([self.PUT0 + k for k in range(C)]) + [int(EMPTY)] * (
            self.pool_size - C
        )
        row[self.pool_off :] = pool
        return jnp.asarray(row[None, :])

    def expand(self, states):
        C, S, M = self.client_count, self.server_count, self.pool_size
        B = states.shape[0]
        u = jnp.uint32
        pool = states[:, self.pool_off :]  # [B, M]
        clients = states[:, self.client_lane]  # [B]

        e = pool  # delivered envelope id per action slot
        idx = jnp.minimum(e, u(self.V - 1)).astype(jnp.int32)
        # ONE packed-table gather; fields unpack with fused shifts/masks
        # (see _build_vocab — seven separate gathers dominated the expand
        # fusion on v5e).
        packed = jnp.take(jnp.asarray(self._PACKED), idx)

        def field(name):
            off, mask = self._field_off[name]
            return (packed >> u(off)) & u(mask)

        typ = field("typ")
        dst = field("dst")
        bal = field("bal")
        prp = field("prp")
        la_m = field("la")
        src = field("src")
        val = field("val")

        # One Deliver action per DISTINCT in-flight envelope (host parity:
        # nonduplicating iter_deliverable yields distinct envelopes). The pool
        # is sorted, so duplicates are adjacent.
        nonempty = e != EMPTY
        first = jnp.concatenate(
            [jnp.ones((B, 1), bool), e[:, 1:] != e[:, :-1]], axis=1
        )
        deliverable = nonempty & first

        is_server_msg = (typ == 0) | (typ == 1) | (typ >= 4)

        # Select the target server's lanes per action slot as a one-hot sum
        # over the S=3 servers — branchless VPU selects fuse; a
        # take_along_axis gather does not.
        srvA_all = states[:, 0 : 2 * S : 2]  # [B, S]
        srvB_all = states[:, 1 : 2 * S : 2]
        d_srv = jnp.where(is_server_msg, dst, 0).astype(jnp.int32)
        sA = jnp.zeros((B, M), u)
        sB = jnp.zeros((B, M), u)
        for s in range(S):
            sel_s = d_srv == s
            sA = jnp.where(sel_s, srvA_all[:, s : s + 1], sA)
            sB = jnp.where(sel_s, srvB_all[:, s : s + 1], sB)
        ballot, prop, accepted, decided, accepts = self._srv_unpack(sA)
        not_dec = decided == 0

        # Per-client fields of the delivered-to client (client msgs).
        csh = (jnp.where(is_server_msg, 0, dst) * 8).astype(jnp.uint32)
        cfield = (clients[:, None] >> csh) & u(0xFF)
        cphase = cfield & u(3)

        # ---- outcome scaffolding -------------------------------------------
        nA, nB = sA, sB  # new server lanes
        ncf = cfield  # new client field
        em1 = jnp.full((B, M), EMPTY)  # up to three emissions
        em2 = jnp.full((B, M), EMPTY)
        em3 = jnp.full((B, M), EMPTY)
        ok = jnp.zeros((B, M), bool)  # transition not elided

        maskS = u((1 << S) - 1)

        def r_of(b):  # ballot code -> round
            return jnp.where(b == 0, u(0), (b - 1) // u(S) + 1)

        # ---- Put (typ 0): propose (ref: examples/paxos.rs:163-183) ----------
        g = (typ == 0) & not_dec & (prop == 0)
        nb = u(1) + r_of(ballot) * u(S) + dst  # (r+1, dst)
        prepB = (u(1) | (accepted << u(1))) << (dst * u(self.bprep)).astype(u)
        nA = jnp.where(g, self._srv_pack(nb, prp + u(1), accepted, u(0), u(0)), nA)
        nB = jnp.where(g, prepB, nB)
        pre0 = u(self.PREPARE0) + (nb - u(1)) * u(S - 1)
        em1 = jnp.where(g, pre0, em1)
        em2 = jnp.where(g, pre0 + u(1), em2)
        ok = ok | g

        # ---- Get (typ 1): reply when decided (ref: paxos.rs:145-157) --------
        g = (typ == 1) & (decided == 1)
        vprop = jnp.where(accepted > 0, (accepted - u(1)) % u(C), u(0))
        em1 = jnp.where(g, u(self.GETOK0) + prp * u(C) + vprop, em1)
        ok = ok | g  # state unchanged; reply makes it a real transition

        # ---- Prepare (typ 4) (ref: paxos.rs:186-192) ------------------------
        g = (typ == 4) & not_dec & (ballot < bal)
        nA = jnp.where(g, self._srv_pack(bal, prop, accepted, u(0), accepts), nA)
        lead = (bal - u(1)) % u(S)
        slot = dst - (dst > lead)
        em1 = jnp.where(
            g,
            u(self.PREPARED0)
            + ((bal - u(1)) * u(S - 1) + slot) * u(self.NLA)
            + accepted,
            em1,
        )
        ok = ok | g

        # ---- Prepared (typ 5) (ref: paxos.rs:193-231) -----------------------
        g = (typ == 5) & not_dec & (bal == ballot)
        sslot = src  # replier server id
        pbit = u(1) << (sslot * u(self.bprep)).astype(u)
        already = (sB & pbit) != 0
        addB = sB | pbit | (la_m << (sslot * u(self.bprep) + u(1)).astype(u))
        # popcount of present bits after insertion
        pres = jnp.zeros((B, M), u)
        best_la = jnp.zeros((B, M), u)
        for j in range(S):
            pj = (addB >> u(j * self.bprep)) & u(1)
            laj = (addB >> u(j * self.bprep + 1)) & u((1 << self.bla) - 1)
            pres = pres + pj
            best_la = jnp.maximum(best_la, jnp.where(pj == 1, laj, u(0)))
        quorum = (~already) & (pres == self.maj)
        chosen = jnp.where(
            best_la > 0, (best_la - u(1)) % u(C), prop - u(1)
        )  # proposal k
        acc0 = u(self.ACCEPT0) + ((bal - u(1)) * u(C) + chosen) * u(S - 1)
        em1 = jnp.where(g & quorum, acc0, em1)
        em2 = jnp.where(g & quorum, acc0 + u(1), em2)
        nA = jnp.where(
            g,
            jnp.where(
                quorum,
                self._srv_pack(
                    ballot,
                    chosen + u(1),
                    u(1) + (bal - u(1)) * u(C) + chosen,  # accepted=(b, chosen)
                    u(0),
                    u(1) << dst,  # accepts = {self}
                ),
                self._srv_pack(ballot, prop, accepted, u(0), accepts),
            ),
            nA,
        )
        nB = jnp.where(g, addB, nB)
        ok = ok | g

        # ---- Accept (typ 6) (ref: paxos.rs:232-240) -------------------------
        g = (typ == 6) & not_dec & (ballot <= bal)
        nacc = u(1) + (bal - u(1)) * u(C) + prp
        nA = jnp.where(g, self._srv_pack(bal, prop, nacc, u(0), accepts), nA)
        lead = (bal - u(1)) % u(S)
        slot = dst - (dst > lead)
        em1 = jnp.where(g, u(self.ACCEPTED0) + (bal - u(1)) * u(S - 1) + slot, em1)
        ok = ok | g

        # ---- Accepted (typ 7) (ref: paxos.rs:241-263) -----------------------
        g = (typ == 7) & not_dec & (bal == ballot)
        abit = u(1) << src
        naccs = (accepts | abit) & maskS
        cnt = jnp.zeros((B, M), u)
        for j in range(S):
            cnt = cnt + ((naccs >> u(j)) & u(1))
        aquorum = ((accepts & abit) == 0) & (cnt == self.maj)
        dec0 = u(self.DECIDED0) + ((bal - u(1)) * u(C) + (prop - u(1))) * u(S - 1)
        em1 = jnp.where(g & aquorum, dec0, em1)
        em2 = jnp.where(g & aquorum, dec0 + u(1), em2)
        em3 = jnp.where(
            g & aquorum, u(self.PUTOK0) + dst * u(C) + (prop - u(1)), em3
        )
        nA = jnp.where(
            g,
            self._srv_pack(
                ballot, prop, accepted, jnp.where(aquorum, u(1), u(0)), naccs
            ),
            nA,
        )
        ok = ok | g

        # ---- Decided (typ 8) (ref: paxos.rs:264-271) ------------------------
        g = (typ == 8) & not_dec
        nacc = u(1) + (bal - u(1)) * u(C) + prp
        nA = jnp.where(g, self._srv_pack(bal, prop, nacc, u(1), accepts), nA)
        ok = ok | g

        # ---- PutOk (typ 2): client advances to Get --------------------------
        # History effects in one transition: on_return(Write) then
        # on_invoke(Read) with the real-time frontier captured from the other
        # clients' CURRENT completed-op counts (ref:
        # src/actor/model.rs:348-357 ordering; linearizability.rs:102-129).
        g = (typ == 2) & (cphase == PH_PUT_INFLIGHT)
        frontier = jnp.zeros((B, M), u)
        fshift = u(0)
        for c2 in range(C):
            # completed ops of client c2: 0 / 1 / 2 by phase
            f2 = (clients[:, None] >> u(8 * c2)) & u(3)
            comp = jnp.where(f2 == PH_DONE, u(2), jnp.where(f2 == PH_GET_INFLIGHT, u(1), u(0)))
            is_peer = dst != c2
            frontier = frontier | jnp.where(is_peer, comp << fshift, u(0))
            # peer slots are assigned in increasing client order, skipping self
            fshift = fshift + jnp.where(is_peer, u(2), u(0))
        ncf = jnp.where(g, u(PH_GET_INFLIGHT) | (frontier << u(4)), ncf)
        em1 = jnp.where(g, u(self.GET0) + dst, em1)
        ok = ok | g

        # ---- GetOk (typ 3): client done -------------------------------------
        g = (typ == 3) & (cphase == PH_GET_INFLIGHT)
        ncf = jnp.where(g, (cfield & ~u(3) & ~u(3 << 2)) | u(PH_DONE) | (val << u(2)), ncf)
        ok = ok | g

        valid = deliverable & ok

        # ---- assemble successors -------------------------------------------
        # Server lanes: scatter the new pair back into the dst server's slot.
        succ = jnp.broadcast_to(states[:, None, :], (B, M, self.lanes))
        srv_sel = (
            jnp.arange(S)[None, None, :] == d_srv[:, :, None]
        ) & is_server_msg[:, :, None]  # [B, M, S]
        newA = jnp.where(srv_sel, nA[:, :, None], srvA_all[:, None, :])
        newB = jnp.where(srv_sel, nB[:, :, None], srvB_all[:, None, :])
        succ = succ.at[:, :, 0 : 2 * S : 2].set(newA)
        succ = succ.at[:, :, 1 : 2 * S : 2].set(newB)

        # Client lane.
        ncl = (
            clients[:, None] & ~(u(0xFF) << csh)
        ) | (ncf << csh)
        ncl = jnp.where(is_server_msg, clients[:, None], ncl)
        succ = succ.at[:, :, self.client_lane].set(ncl)

        # Pool: drop the delivered slot, add emissions, restore the
        # canonical sorted-multiset form via the unrolled rank-sort
        # (tensor/poolops.py — a jnp.sort along the minor axis was the
        # single largest slice of this kernel's fusion on v5e). pool_size
        # has slack over the measured max in-flight; if a successor would
        # exceed it anyway, the row becomes the reserved all-ones POISON
        # state (terminal — its pool is all EMPTY) and the "pool capacity"
        # property below reports it as a discovery instead of silently
        # truncating the state space.
        act = jnp.arange(M, dtype=jnp.uint32)[None, :]
        parts = [
            jnp.where(act == i, EMPTY, pool[:, i : i + 1]) for i in range(M)
        ] + [em1, em2, em3]
        npool, overflow = rank_sort(parts, M)
        succ = succ.at[:, :, self.pool_off :].set(npool)
        succ = jnp.where(overflow[:, :, None], jnp.uint32(EMPTY), succ)

        return succ, valid

    # -- properties ------------------------------------------------------------

    def properties(self):
        C = self.client_count

        def linearizable(model, states):
            clients = states[:, model.client_lane]
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

            pm = jnp.asarray(model._lin_phase)  # [NC, C]
            exp = jnp.asarray(model._lin_ret)  # [NC, C]
            maxf = jnp.asarray(model._lin_maxf)  # [NC, C, C]

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
            # Poison (pool-overflow) rows are reported by "pool capacity",
            # not as spurious linearizability violations.
            return jnp.any(combo_ok, axis=1) | _is_poison(states)

        def value_chosen(model, states):
            pool = states[:, model.pool_off :]
            return jnp.any(
                (pool >= model.GETOK0) & (pool < model.GETOK0 + C * C), axis=1
            )

        def _is_poison(states):
            return jnp.all(states == jnp.uint32(EMPTY), axis=1)

        def pool_capacity(model, states):
            return ~_is_poison(states)

        return [
            TensorProperty.always("linearizable", linearizable),
            TensorProperty.sometimes("value chosen", value_chosen),
            TensorProperty.always("pool capacity", pool_capacity),
        ]

    # -- display ---------------------------------------------------------------

    def decode(self, row):
        C, S = self.client_count, self.server_count
        row = [int(x) for x in row]
        servers = []
        for s in range(S):
            a, b = row[2 * s], row[2 * s + 1]
            ballot = a & ((1 << self.bb) - 1)
            servers.append(
                dict(
                    ballot=ballot,
                    proposal=(a >> self.off_prop) & 3,
                    accepted=(a >> self.off_acc) & ((1 << self.bla) - 1),
                    decided=(a >> self.off_dec) & 1,
                    accepts=(a >> self.off_accs) & ((1 << S) - 1),
                    prepares=[
                        (
                            (b >> (j * self.bprep)) & 1,
                            (b >> (j * self.bprep + 1)) & ((1 << self.bla) - 1),
                        )
                        for j in range(S)
                    ],
                )
            )
        clients = []
        for c in range(C):
            f = (row[self.client_lane] >> (8 * c)) & 0xFF
            clients.append(dict(phase=f & 3, ret=(f >> 2) & 3, frontier=f >> 4))
        pool = [x for x in row[self.pool_off :] if x != int(EMPTY)]
        return dict(servers=servers, clients=clients, network=pool)

    def action_label(self, row, action_index):
        e = int(row[self.pool_off + action_index])
        if e == int(EMPTY):
            return "noop"
        names = ["Put", "Get", "PutOk", "GetOk", "Prepare", "Prepared", "Accept", "Accepted", "Decided"]
        return f"Deliver({int(self._SRC[e])}->{int(self._DST[e])}, {names[int(self._TYP[e])]}#{e})"
