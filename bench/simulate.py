"""Inputs from a seed: reference genomes and simulated reads.

The error model is the one of the paper's §VI-A datasets (Table II), as
the program's own simulator applies it: each base of the source span is
independently deleted, preceded by a random inserted base, or
substituted by one of the three other bases, at the profile's rates; a
read may then be reverse-complemented. Here it runs vectorised over a
whole block of reads, so a pool of tens of thousands of reads costs
seconds of set-up, not minutes.

Every function takes a numpy Generator; the harness derives one per
purpose from the run's `--seed`, so the same seed gives the same inputs.
"""

from __future__ import annotations

import dataclasses

import numpy as np

#: Bases corrupted per vectorised block when simulating a pool.
BLOCK_BASES = 1 << 22


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per (seed, purpose); any whole seed."""
    words = [int(seed) % (1 << 64)] + [ord(c) for c in stream]
    return np.random.default_rng(np.random.SeedSequence(words))


def random_genome(length: int, rng: np.random.Generator) -> np.ndarray:
    """A uniform random genome in the 2-bit alphabet (A=0 C=1 G=2 T=3)."""
    return rng.integers(0, 4, size=int(length), dtype=np.int8)


def reverse_complement(seq: np.ndarray) -> np.ndarray:
    return (3 - np.asarray(seq, np.int8))[::-1].copy()


@dataclasses.dataclass
class ReadBlock:
    """Simulated reads with their truth: `reads[k]` was drawn from
    genome[loci[k]:loci[k] + spans[k]], and reverse-complemented where
    strands[k] == 1."""

    reads: list
    loci: np.ndarray
    spans: np.ndarray
    strands: np.ndarray

    def __len__(self) -> int:
        return len(self.reads)


def corrupt(sources: list, rates: dict, rng: np.random.Generator) -> list:
    """Apply the per-base error model to each source span; returns the
    reads (int8 arrays). One random draw per base decides its fate, in
    the order deletion, insertion, substitution."""
    lens = np.array([len(s) for s in sources], np.int64)
    base = np.concatenate(sources).astype(np.int8)
    roll = rng.random(base.size)
    p_del = rates["del"]
    p_ins = p_del + rates["ins"]
    p_sub = p_ins + rates["sub"]
    deleted = roll < p_del
    inserted = (roll >= p_del) & (roll < p_ins)
    subbed = (roll >= p_ins) & (roll < p_sub)
    shift = rng.integers(1, 4, size=base.size, dtype=np.int8)
    out_base = np.where(subbed, (base + shift) % 4, base).astype(np.int8)
    counts = np.where(deleted, 0, np.where(inserted, 2, 1))
    flat = np.repeat(out_base, counts)
    firsts = np.cumsum(counts) - counts
    flat[firsts[inserted]] = rng.integers(0, 4, size=int(inserted.sum()),
                                          dtype=np.int8)
    ends = np.cumsum(counts)
    read_ends = ends[np.cumsum(lens) - 1]
    read_starts = np.concatenate([[0], read_ends[:-1]])
    return [flat[a:b] for a, b in zip(read_starts, read_ends)]


def sample_reads(genome: np.ndarray, spans, rates: dict, rc_prob: float,
                 rng: np.random.Generator) -> ReadBlock:
    """One read per entry of `spans`, from a uniform locus."""
    spans = np.asarray(spans, np.int64)
    loci = rng.integers(0, genome.size - spans + 1)
    sources = [genome[a:a + s] for a, s in zip(loci, spans)]
    # Blocks of about BLOCK_BASES bases bound the temporaries.
    cuts = np.searchsorted(np.cumsum(spans),
                           np.arange(BLOCK_BASES, spans.sum(), BLOCK_BASES))
    reads = []
    for part in np.split(np.arange(spans.size), np.unique(cuts)):
        if part.size:
            reads += corrupt([sources[k] for k in part], rates, rng)
    strands = (rng.random(spans.size) < rc_prob).astype(np.int64)
    reads = [reverse_complement(r) if st else r
             for r, st in zip(reads, strands)]
    return ReadBlock(reads=reads, loci=loci, spans=spans, strands=strands)


def stratified_spans(count: int, lo: int, hi: int, block: int,
                     rng: np.random.Generator) -> np.ndarray:
    """Span lengths uniform over [lo, hi], stratified per block: every
    block of `block` spans holds the same evenly spaced lengths in a
    seeded order, so any run that consumes whole blocks does the same
    amount of work whatever its seed."""
    grid = lo + ((np.arange(block) + 0.5) * (hi - lo + 1) / block
                 ).astype(np.int64)
    blocks = [rng.permutation(grid) for _ in range(-(-count // block))]
    return np.concatenate(blocks)[:count]


def pair_spans(traffic: dict, count: int, rng: np.random.Generator):
    """Source span lengths of a mix: `read_len` for a fixed length, or
    `span_min`..`span_max` stratified per `span_block`."""
    if "read_len" in traffic:
        return np.full(count, int(traffic["read_len"]), np.int64)
    return stratified_spans(count, int(traffic["span_min"]),
                            int(traffic["span_max"]),
                            int(traffic["span_block"]), rng)


def pair_pool(genome: np.ndarray, traffic: dict, count: int,
              rng: np.random.Generator) -> tuple[ReadBlock, list]:
    """`count` (read, true window) pairs of a mix: the reads, and the
    forward-strand windows they were drawn from."""
    block = sample_reads(genome, pair_spans(traffic, count, rng),
                         traffic["rates"], float(traffic.get("rc_prob", 0.0)),
                         rng)
    refs = [genome[a:a + s] for a, s in zip(block.loci, block.spans)]
    return block, refs
