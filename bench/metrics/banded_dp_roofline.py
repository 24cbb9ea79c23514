"""The banded DP kernel's share of its roofline, in %: the least time
the chip needs for the window's alignments (roofline.py counts their
work from true lengths) over the kernel's device time in the trace,
summed over devices."""

import roofline
import trace_reduce


def read(run):
    if run.trace is None:
        return None
    kernel_s = trace_reduce.named_seconds(run.trace, "banded_dp")
    n, m, band = run.work
    ops, nbytes = roofline.work(n, m, band)
    t_min, _ = roofline.least_time(ops, nbytes,
                                   roofline.peaks(run.device_kind))
    return 100.0 * t_min / kernel_s
