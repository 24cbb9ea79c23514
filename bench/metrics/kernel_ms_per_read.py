"""Device milliseconds of the banded DP kernel per completed read, from
the trace (summed over devices)."""

import trace_reduce


def read(run):
    if run.trace is None:
        return None
    s = trace_reduce.named_seconds(run.trace, "banded_dp")
    return s * 1e3 / max(run.record["reads"], 1)
