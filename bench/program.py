"""The system under test, built from a configuration file.

The only module of the benchmark that imports the program, and only
through its public API: `AlignmentEngine`, `AlignmentService`,
`MinimizerIndex`, `ReadMapper`, `configs.rapidx.CONFIG` and
`enable_compilation_cache`. Settings come from the configuration file,
which copies the launcher's own (`launch/map.py`, `launch/serve.py`).
"""

from __future__ import annotations

import pathlib
import sys

import numpy as np

from loader import BenchError

_SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)


def enable_cache() -> str:
    from repro.core.engine import enable_compilation_cache
    return enable_compilation_cache()


def check_scoring(config: dict) -> None:
    """The program's scoring preset must be the one the configuration
    states, since the reference scores with the configuration's."""
    from repro.configs.rapidx import CONFIG as RAPIDX
    sc = RAPIDX.scoring
    have = dict(match=sc.match, mismatch=sc.mismatch,
                gap_open=sc.gap_open, gap_extend=sc.gap_extend)
    if have != config["scoring"]:
        raise BenchError(f"program scoring {have} differs from the "
                         f"configuration's {config['scoring']}")


def make_service(config: dict, devices):
    """An `AlignmentService` over an `AlignmentEngine` as the config
    sets them up. `mesh_devices` > 0 shards the engine over that many
    devices on a ("data", "model") mesh, as `launch/serve.py` does."""
    import jax
    from jax.sharding import Mesh
    from repro.configs.rapidx import CONFIG as RAPIDX
    from repro.core.engine import AlignmentEngine
    from repro.serve import AlignmentService

    check_scoring(config)
    mesh = None
    n_mesh = int(config.get("mesh_devices", 0))
    if n_mesh:
        if len(devices) < n_mesh:
            raise BenchError(f"config wants {n_mesh} devices, "
                             f"{len(devices)} given")
        mesh = Mesh(np.asarray(devices[:n_mesh]).reshape(n_mesh, 1),
                    ("data", "model"))
    engine = AlignmentEngine(backend="auto", sc=RAPIDX.scoring,
                             capacity=int(config["capacity"]), mesh=mesh,
                             xdrop=config.get("xdrop"),
                             base_bandwidth=config.get("base_bandwidth"))
    if jax.devices()[0].platform == "tpu" and engine.backend_name != "pallas":
        raise BenchError(f"backend resolved to {engine.backend_name} on a TPU")
    depth = config["depth"]
    service = AlignmentService(
        engine, mode=config["mode"], collect_tb=bool(config["collect_tb"]),
        max_wait_ms=float(config["max_wait_ms"]), policy=config["policy"],
        max_inflight_groups=depth if depth == "auto" else int(depth))
    return service


def warm_service(service, reads, refs) -> None:
    """Compile the dispatch programs of each length class at the longest
    (read, ref) lengths these pairs have in it, before traffic, as
    `launch/serve.py --warmup` does."""
    q = np.array([len(x) for x in reads])
    r = np.array([len(x) for x in refs])
    longest = [(int(q[g.indices].max()), int(r[g.indices].max()))
               for g in service.engine.plan(q, r)]
    service.engine.warmup(longest, mode=service.mode,
                          collect_tb=service.collect_tb)


def make_index(genome: np.ndarray, config: dict):
    from repro.map import MinimizerIndex
    return MinimizerIndex(genome, k=int(config["k"]), w=int(config["w"]),
                          max_occ=int(config["max_occ"]))


def make_mapper(index, service, config: dict):
    from repro.map import ReadMapper
    return ReadMapper(index, service, window_pad=int(config["window_pad"]),
                      priorities=tuple(config["priorities"]))


def mapped_status() -> str:
    from repro.map import STATUS_MAPPED
    return STATUS_MAPPED


def service_counters(service) -> dict:
    """The service's cumulative counters (window deltas are taken by the
    caller; its latency reservoirs run from service start and are not
    used)."""
    st = service.stats()
    return {k: st[k] for k in ("submitted", "completed", "dispatches",
                               "real_pairs", "padded_slots",
                               "bytes_fetched")}
