"""1 - busy / window, averaged over the cell's devices, from the trace:
busy is the union of the intervals in which an op ran."""


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    busy = [d["busy_s"] for d in run.trace["devices"]]
    return 1.0 - sum(busy) / len(busy) / run.trace["window_s"]
