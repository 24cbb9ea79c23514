"""Real pairs over padded dispatch slots in the window (deltas of the
service's counters)."""


def read(run):
    c = run.record["counters"]
    if not c["padded_slots"]:
        return None
    return c["real_pairs"] / c["padded_slots"]
