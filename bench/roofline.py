"""The work the banded DP needs, counted from what it is asked, and the
least time the chip could do it in.

Work is counted from the true lengths of the real pairs, whatever runs
them: each pair sweeps n + m anti-diagonals of B band cells. Padded
slots, padded lanes and the trimmed sweep's rounding are not work, so a
change of layout is judged against unchanged work.

Operations per cell update, from the shifted recurrence (paper Eq. 4)
that the kernel evaluates:

    s    = q == r ? A : -B           compare, select           2
    A'   = max(s + 2(o+e), x', y')   add, max, max             3
    dH'  = A' - dV'_up               subtract                  1
    dV'  = A' - dH'_left             subtract                  1
    dE'  = max(A', x'_up + o) - dH'_left    add, max, subtract  3
    dF'  = max(A', y'_left + o) - dV'_up    add, max, subtract  3
    H    = H_up + dH' - (o+e)        add, subtract             2
                                                              --
                                                              15

(the 15 of `roofline/analytic.py`'s `alignment_roofline`). Masks,
band moves and the traceback flags are how the kernel does it, not
what the algorithm needs, and are not counted.

Bytes: each pair's read and window come in once (one byte per base),
and its traceback plane of 4-bit flags, n + m rows of ceil(B / 2)
bytes, is written by the kernel and read back once by the device walker.
A layout that packed two cells into one 32-bit lane would need a peak
for packed operations, which `peaks.json` does not hold.
"""

from __future__ import annotations

import json
import pathlib

import numpy as np

OPS_PER_CELL = 15
PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


def work(n, m, band) -> tuple[float, float]:
    """(int32 operations, HBM bytes) that these pairs need."""
    n = np.asarray(n, np.float64)
    m = np.asarray(m, np.float64)
    band = np.asarray(band, np.float64)
    steps = n + m
    ops = OPS_PER_CELL * float(np.sum(steps * band))
    plane = float(np.sum(steps * np.ceil(band / 2)))
    return ops, float(np.sum(n + m)) + 2 * plane


def peaks(device_kind: str) -> dict:
    """The peak table's entry for this device kind; an unknown kind is
    an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}")
    return table[device_kind]


def least_time(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least seconds the chip needs, and which peak bounds it."""
    t_ops = ops / peak["vpu_int32_ops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "vpu") if t_ops >= t_bytes else (t_bytes, "hbm")
