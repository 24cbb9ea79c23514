"""Ahead-of-time compiles of the device programs for a described TPU v5e.

The TPU compiler is installed with JAX, and it compiles for a chip that is
described rather than attached, so these tests catch what interpret mode
cannot: an op Mosaic does not lower, a block shape off the tiling, a
kernel over its fast-memory budget. Nothing runs; results and times come
only from a run on the chip (`python chip_smoke.py`).

The topology is described inside a module-scoped fixture, never at import
time: only one process at a time may load the TPU library, and every
test worker imports this file.
"""

import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.scoring import MINIMAP2
from repro.core.traceback_device import decode_packed_tb
from repro.kernels.banded_dp.banded_dp import banded_align_pallas
from repro.kernels.banded_dp.persistent import persistent_align_pallas

#: (padded length, band, t_max) of the per-group kernel: a 150 bp
#: short-read class and the 8192 long-read class at the band cap.
SHORT = (256, 20, 384)
LONG = (8192, 100, 14848)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        with pytest.MonkeyPatch.context() as mp:
            # The TPU library reads this as it loads; without it the
            # compiler writes its logs under /tmp.
            if "TPU_LOG_DIR" not in os.environ:
                mp.setenv("TPU_LOG_DIR", "disabled")
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off.
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *specs) -> str:
    return jax.jit(fn).lower(*specs).compile().as_text()


@pytest.mark.parametrize("geometry", [SHORT, LONG], ids=["b20", "b100"])
@pytest.mark.parametrize("collect_tb", [True, False], ids=["tb", "score"])
def test_wavefront_kernel_compiles(one_chip, geometry, collect_tb):
    L, band, t_max = geometry
    seq = _spec(one_chip, (64, L), jnp.int8)
    lens = _spec(one_chip, (64,), jnp.int32)

    def run(q, r, n, m):
        return banded_align_pallas(q, r, n, m, sc=MINIMAP2, band=band,
                                   collect_tb=collect_tb, t_max=t_max,
                                   interpret=False)

    assert "tpu_custom_call" in _compile(run, seq, seq, lens, lens)


def test_wavefront_kernel_narrow_xdrop_compiles(one_chip):
    L, band, t_max = LONG
    seq = _spec(one_chip, (64, L), jnp.int8)
    lens = _spec(one_chip, (64,), jnp.int32)

    def run(q, r, n, m):
        return banded_align_pallas(q, r, n, m, sc=MINIMAP2, band=band,
                                   mode="semiglobal", t_max=t_max,
                                   cell_dtype="narrow", xdrop=60,
                                   interpret=False)

    assert "tpu_custom_call" in _compile(run, seq, seq, lens, lens)


def test_persistent_kernel_compiles(one_chip):
    # Two groups: (q_len, r_len, band, t_max, padded rows).
    geom = ((256, 256, 20, 384, 64), (1024, 1024, 30, 1536, 24))
    bt, chunk, nb = 8, 128, 8
    band = np.array([g[2] for g in geom], np.int32)
    chunks = np.array([-(-g[3] // chunk) for g in geom], np.int32)
    tiles = np.array([-(-g[4] // bt) for g in geom], np.int32)
    seq = _spec(one_chip, (len(geom), nb, bt, 1024), jnp.int8)
    lens = _spec(one_chip, (len(geom), nb, bt, 1), jnp.int32)

    def run(q, r, n, m):
        return persistent_align_pallas(
            q, r, n, m, band, chunks, tiles, sc=MINIMAP2, geom=geom, bt=bt,
            chunk=chunk, adaptive=True, collect_tb=True, mode="semiglobal",
            cell_dtype="narrow", xdrop=60, interpret=False)

    assert "tpu_custom_call" in _compile(run, seq, seq, lens, lens)


def test_device_traceback_walker_compiles(one_chip):
    N, T, band = 8, 320, 20
    tb = _spec(one_chip, (N, T, (band + 1) // 2), jnp.uint8)
    los = _spec(one_chip, (N, T + 1), jnp.int32)
    start = _spec(one_chip, (N,), jnp.int32)
    decode_packed_tb.lower(tb, los, start, start, band=band).compile()


def test_chain_program_compiles(one_chip):
    from repro.map.chain import ChainParams, _chain_batch_fn

    p = ChainParams()
    fn = _chain_batch_fn(p.k, p.max_gap, p.max_diag_diff)
    pos = _spec(one_chip, (16, p.anchors_cap), jnp.int32)
    valid = _spec(one_chip, (16, p.anchors_cap), jnp.bool_)
    fn.lower(pos, pos, valid).compile()
