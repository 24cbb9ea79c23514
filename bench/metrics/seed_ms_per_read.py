"""Host milliseconds spent in `MinimizerIndex.lookup` per read of the
window (both strands), timed on the harness's own index instance."""


def read(run):
    if "seed_s" not in run.record:
        return None
    return run.record["seed_s"] * 1e3 / max(run.record["reads"], 1)
