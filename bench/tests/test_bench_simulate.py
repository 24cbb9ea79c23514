"""The simulator's error rates, and determinism of every input in the
seed."""

import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import simulate  # noqa: E402

PACBIO = {"sub": 0.015, "ins": 0.09, "del": 0.045}


def test_error_rates_match_the_profile():
    rng = simulate.rng_for(3, "t")
    genome = simulate.random_genome(100_000, rng)
    src = [genome[k * 1000:(k + 1) * 1000] for k in range(100)]
    reads = simulate.corrupt(src, PACBIO, rng)
    bases = 100 * 1000
    # Each inserted base lengthens the read, each deleted one shortens it.
    grow = (sum(len(r) for r in reads) - bases) / bases
    assert grow == pytest.approx(PACBIO["ins"] - PACBIO["del"], abs=0.005)
    # Reads without indels keep positions: count substitutions there.
    same = [(s, r) for s, r in zip(
        src, simulate.corrupt(src, {"sub": 0.03, "ins": 0.0, "del": 0.0},
                              rng))]
    subs = np.mean([np.mean(s != r) for s, r in same])
    assert subs == pytest.approx(0.03, abs=0.003)


def test_reads_and_truth_are_deterministic_in_the_seed():
    def draw(seed):
        rng = simulate.rng_for(seed, "pool")
        g = simulate.random_genome(50_000, rng)
        return simulate.sample_reads(g, [150] * 50, PACBIO, 0.5, rng)
    a, b, c = draw(2**31 + 7), draw(2**31 + 7), draw(2**31 + 8)
    assert all(np.array_equal(x, y) for x, y in zip(a.reads, b.reads))
    assert np.array_equal(a.loci, b.loci)
    assert np.array_equal(a.strands, b.strands)
    assert not np.array_equal(a.loci, c.loci)
    assert 0 < a.strands.sum() < 50


def test_reverse_strand_reads_come_from_their_locus():
    rng = simulate.rng_for(5, "rc")
    g = simulate.random_genome(20_000, rng)
    clean = {"sub": 0.0, "ins": 0.0, "del": 0.0}
    block = simulate.sample_reads(g, [100] * 20, clean, 0.5, rng)
    for read, locus, strand in zip(block.reads, block.loci, block.strands):
        fwd = simulate.reverse_complement(read) if strand else read
        assert np.array_equal(fwd, g[locus:locus + 100])


def test_span_blocks_hold_the_same_lengths_in_seeded_orders():
    a = simulate.stratified_spans(1024, 2000, 10000, 512,
                                  simulate.rng_for(1, "s"))
    b = simulate.stratified_spans(1024, 2000, 10000, 512,
                                  simulate.rng_for(2, "s"))
    assert sorted(a[:512]) == sorted(b[:512]) == sorted(a[512:])
    assert not np.array_equal(a, b)
    assert a.min() >= 2000 and a.max() <= 10000
