"""A decoded two-phase-commit state of the program's answer — (resource
manager states, transaction manager state, prepared mask, messages in
flight) — as the fields the reference reads: rm_0..rm_{N-1} tm
prepared_mask commit abort prepared_msg_0..prepared_msg_{N-1}."""

RM = {"working": 0, "prepared": 1, "committed": 2, "aborted": 3}
TM = {"init": 0, "committed": 1, "aborted": 2}


def encode(state) -> list:
    rms, tm, prepared, msgs = state
    n = len(rms)
    return ([RM[r] for r in rms] + [TM[tm], int(prepared),
            int("commit" in msgs), int("abort" in msgs)]
            + [int(f"prepared({i})" in msgs) for i in range(n)])
