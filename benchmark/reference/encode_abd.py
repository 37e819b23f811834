"""A decoded ABD state of the program's answer (the dict that a discovery
path's states hold: servers, clients, network) as the fields the
reference (bfs_ref_abd.cpp) reads: per server clock id val phase req
requester write read acks_mask, then per server slot j present clock id
val (its phase-1 responses); per client phase ret and its Get's frontier
toward each client (0 toward itself); then per envelope type src dst req
clock id val. Value codes: 0 NULL, 1+k 'A'+k, 15 none in a server's
write and read, 0 none in an envelope."""

TYPES = ("Put", "Get", "PutOk", "GetOk", "Query", "AckQuery", "Record",
         "AckRecord")
NONE = 15


def _value(v, none=NONE) -> int:
    if v is None:
        return none
    return 0 if v == "\x00" else ord(v) - ord("A") + 1


def encode(state) -> list:
    servers, clients = state["servers"], state["clients"]
    S, C = len(servers), len(clients)
    out = []
    for s in servers:
        out += [*s["seq"], _value(s["val"]), s["phase"],
                s.get("request_id", 0), s.get("requester", 0),
                _value(s.get("write")), _value(s.get("read")),
                sum(1 << j for j in s.get("acks", ()))]
        resp = {j: (seq, v) for j, seq, v in s.get("responses", ())}
        for j in range(S):
            seq, v = resp.get(j, ((0, 0), "\x00"))
            out += [int(j in resp), *seq, _value(v)]
    for k, c in enumerate(clients):
        # The Get's return is the writing client's index; 3 is NULL.
        ret = (0 if c["ret"] == 3 else c["ret"] + 1) if c["phase"] == 2 else 0
        peers = [j for j in range(C) if j != k]
        frontier = [0] * C
        for i, j in enumerate(peers):
            frontier[j] = (c["frontier"] >> (2 * i)) & 3
        out += [c["phase"], ret, *frontier]
    for name, src, dst, req, seq, val in state["network"]:
        out += [TYPES.index(name), src, dst, req, *(seq or (0, 0)),
                _value(val, none=0)]
    return out
