"""Tile-level parallelism (paper Fig. 6(a)) — shard_map over the device mesh.

RAPIDx distributes kt sequence batches over 64 independent tiles with *no
inter-tile communication*; the TPU analogue shards the batch dimension of
an alignment dispatch over the mesh's data axes with `shard_map`. Because
alignment is embarrassingly parallel, the lowered program contains zero
collectives — asserted by tests and visible in the roofline table (the
collective term of the alignment workload is 0).

Also hosts the alignment serve-step used by the dry-run: the production
mesh's ("pod", "data") axes both shard the batch; the "model" axis is
unused (replicated) for alignment, matching the paper's single-tile
independence.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.core.scoring import ScoringConfig, MINIMAP2


def shard_map(f, *, mesh, in_specs, out_specs):
    """`jax.shard_map` with replication checking disabled."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_aligner(mesh: Mesh, sc: ScoringConfig = MINIMAP2, *, band: int,
                 adaptive: bool = True, collect_tb: bool = False,
                 batch_axes: tuple[str, ...] | None = None,
                 backend: str = "reference",
                 backend_opts: dict | None = None,
                 t_max: int | None = None, decode: str = "host"):
    """Builds a pjit-able batched aligner sharded over the mesh.

    A thin wrapper over `AlignmentEngine(mesh=...)`: the returned
    callable is the engine's cached jit'd shard_map program for this
    dispatch signature (`AlignmentEngine.sharded_runner`). The engine's
    ragged `align` path shards its dispatch groups through the very same
    machinery.

    Args:
      mesh: device mesh; the batch shards over `batch_axes`.
      batch_axes: mesh axes to shard the batch over. Defaults to all axes
        named "pod"/"data" present in the mesh (alignment never uses
        "model" — a tile needs no partner).
      backend: engine execution backend run on each shard ('reference',
        'pallas', 'auto'); the backend contract is jax-traceable, so the
        same shard_map wrapper serves every path.
      t_max: optional trimmed sweep length (>= max true n + m of every
        batch the aligner will see).
      decode: traceback decode stage when collect_tb — "host" returns the
        raw packed planes, "device" fuses the lockstep walker under the
        same shard_map and returns RLE CIGAR arrays (still zero
        collectives: the walk is per-pair).
    """
    from repro.core.engine import AlignmentEngine

    eng = AlignmentEngine(backend=backend, sc=sc, adaptive=adaptive,
                          backend_opts=backend_opts, mesh=mesh,
                          batch_axes=batch_axes)
    return eng.sharded_runner(band=band, collect_tb=collect_tb,
                              t_max=t_max, decode=decode)


def alignment_serve_step(mesh: Mesh, sc: ScoringConfig = MINIMAP2, *,
                         band: int, collect_tb: bool = False):
    """The alignment-as-a-service step for launch/serve.py and the dry-run.

    Input: a padded dispatch batch (global). Output: scores (+ optional
    traceback planes), sharded the same way.
    """
    return make_aligner(mesh, sc, band=band, collect_tb=collect_tb)


def alignment_input_specs(global_batch: int, q_len: int, r_len: int):
    """ShapeDtypeStructs for the dry-run (no allocation)."""
    return (
        jax.ShapeDtypeStruct((global_batch, q_len), jnp.int8),
        jax.ShapeDtypeStruct((global_batch, r_len), jnp.int8),
        jax.ShapeDtypeStruct((global_batch,), jnp.int32),
        jax.ShapeDtypeStruct((global_batch,), jnp.int32),
    )
