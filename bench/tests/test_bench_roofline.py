"""The roofline's count of work, against pairs worked by hand."""

import json
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import roofline  # noqa: E402


def test_one_pair_by_hand():
    # n=150, m=150, B=20: 300 anti-diagonals of 20 cells = 6,000 cells,
    # 15 ops each; bytes: 300 bases in, 300 rows of 10 plane bytes
    # written and read back.
    ops, nbytes = roofline.work([150], [150], [20])
    assert ops == 15 * 300 * 20
    assert nbytes == 300 + 2 * 300 * 10


def test_odd_band_rounds_plane_bytes_up_and_pairs_add():
    ops, nbytes = roofline.work([100, 7000], [90, 6800], [21, 100])
    assert ops == 15 * (190 * 21 + 13800 * 100)
    assert nbytes == (190 + 13800) + 2 * (190 * 11 + 13800 * 50)


def test_least_time_names_its_bound():
    peak = {"vpu_int32_ops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    assert roofline.least_time(2e12, 1e10, peak) == (2.0, "vpu")
    assert roofline.least_time(1e11, 5e11, peak) == (5.0, "hbm")


def test_peak_table_has_sources_and_refuses_unknown_kinds():
    table = json.loads(roofline.PEAKS_FILE.read_text())
    for kind, entry in table.items():
        assert entry["hbm_source"] and entry["vpu_source"], kind
        assert roofline.peaks(kind) == entry
    with pytest.raises(KeyError):
        roofline.peaks("no such chip")
