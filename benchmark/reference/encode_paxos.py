"""A decoded Paxos state of the program's answer (the dict that a
discovery path's states hold: servers, clients, network) as the fields the
reference reads: per server ballot proposal accepted decided accepts and
(present, last accepted) of each of the 3 prepares; per client phase ret
frontier; then the envelope ids in flight."""


def encode(state) -> list:
    out = []
    for s in state["servers"]:
        out += [s["ballot"], s["proposal"], s["accepted"], s["decided"],
                s["accepts"]]
        for present, la in s["prepares"]:
            out += [present, la]
    for c in state["clients"]:
        out += [c["phase"], c["ret"], c["frontier"]]
    return out + list(state["network"])
