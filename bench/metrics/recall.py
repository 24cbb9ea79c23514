"""Share of the window's reads mapped on the simulator's true strand and
within the alignment band of the true locus (the rule of
tests/test_mapper.py)."""


def read(run):
    if "recall_hits" not in run.record:
        return None
    return run.record["recall_hits"] / max(run.record["reads"], 1)
