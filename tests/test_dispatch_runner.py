"""The engine's single-device dispatch program (`AlignmentEngine.runner`).

Without a mesh every dispatch slice runs `jit(banded_dispatch)`: the
backend's kernel, its layout ops and the traceback walker as one program,
built once per dispatch signature (band, sweep, mode, traceback, decode)
and then only dispatched. These tests count the programs built
(`repro.obs.programs_built`, and the `builds` stat of `serve.enqueue`)
and hold the cached program to the eager backend call bit for bit.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro import obs
from repro.core.batch import pad_group, run_dispatch
from repro.core.engine import SCALAR_KEYS, AlignmentEngine
from repro.serve import AlignmentService
from test_obs import _host_spans

# Small tiles keep the interpret-mode kernel affordable on CPU.
BACKENDS = {"reference": None, "pallas": {"batch_tile": 4, "chunk": 64}}
DISPATCH = "jit(banded_dispatch)"


def _pairs(count, length=48, seed=0):
    """`count` (read, ref) pairs of one length: every other read is its
    reference with a few substitutions, the rest unrelated (so an xdrop
    engine retires some)."""
    rng = np.random.default_rng(seed)
    reads, refs = [], []
    for k in range(count):
        ref = rng.integers(0, 4, length).astype(np.int8)
        if k % 2:
            read = rng.integers(0, 4, length).astype(np.int8)
        else:
            read = ref.copy()
            hits = rng.choice(length, 3, replace=False)
            read[hits] = (read[hits] + 1) % 4
        reads.append(read)
        refs.append(ref)
    return reads, refs


def _engine(backend, **kw):
    return AlignmentEngine(backend=backend, capacity=4,
                           backend_opts=BACKENDS[backend], **kw)


def _built_since(before: dict) -> dict:
    """{fun_name: programs built} since the `programs_built()` snapshot."""
    return {fun: b["count"] - before.get(fun, {"count": 0})["count"]
            for fun, b in obs.programs_built().items()
            if b["count"] != before.get(fun, {"count": 0})["count"]}


def _enqueue_builds(eng, reads, refs, spec) -> dict:
    before = obs.programs_built()
    pd = eng.enqueue_group(reads, refs, spec, mode="semiglobal",
                           collect_tb=True)
    built = _built_since(before)
    eng.finalize_group(pd)
    return built


@pytest.mark.parametrize("backend", sorted(BACKENDS))
def test_second_enqueue_of_a_signature_builds_nothing(backend):
    eng = _engine(backend)
    reads, refs = _pairs(6, seed=1)
    [group] = eng.plan([len(x) for x in reads], [len(x) for x in refs])
    assert _enqueue_builds(eng, reads, refs, group.spec) == {DISPATCH: 1}
    other_reads, other_refs = _pairs(5, seed=2)
    assert _enqueue_builds(eng, other_reads, other_refs, group.spec) == {}


@pytest.mark.parametrize("backend", sorted(BACKENDS))
@pytest.mark.parametrize("change", ["t_max", "band"])
def test_a_new_sweep_or_band_builds_one_program(backend, change):
    eng = _engine(backend)
    reads, refs = _pairs(4, seed=3)
    [group] = eng.plan([len(x) for x in reads], [len(x) for x in refs])
    _enqueue_builds(eng, reads, refs, group.spec)
    spec = group.spec
    new = dataclasses.replace(spec, **{change: getattr(spec, change) + 4})
    assert _enqueue_builds(eng, reads, refs, new) == {DISPATCH: 1}
    assert _enqueue_builds(eng, reads, refs, new) == {}


@pytest.mark.parametrize("backend", sorted(BACKENDS))
@pytest.mark.parametrize("mode", ["global", "semiglobal"])
@pytest.mark.parametrize("collect_tb", [False, True], ids=["score", "tb"])
@pytest.mark.parametrize("xdrop", [None, 12], ids=["full", "xdrop"])
def test_cached_program_matches_the_eager_backend_call(backend, mode,
                                                       collect_tb, xdrop):
    eng = _engine(backend, xdrop=xdrop)
    reads, refs = _pairs(6, seed=4)
    [group] = eng.plan([len(x) for x in reads], [len(x) for x in refs])
    spec = group.spec
    got = eng.finalize_group(eng.enqueue_group(
        reads, refs, spec, mode=mode, collect_tb=collect_tb))
    want = run_dispatch(eng.backend, *pad_group(reads, refs, spec),
                        sc=eng.sc, band=spec.band, capacity=spec.capacity,
                        num_real=len(reads), adaptive=eng.adaptive,
                        collect_tb=collect_tb, mode=mode, t_max=spec.t_max,
                        decode=eng.decode, xdrop=xdrop)
    for key in SCALAR_KEYS:
        np.testing.assert_array_equal(got[key], want[key], err_msg=key)
    if xdrop is not None:
        assert (got["status"] != 0).any() and (got["status"] == 0).any()
    if collect_tb:
        assert got["cigars"] == want["cigars"]
        assert any(c is not None for c in got["cigars"])
    else:
        assert "cigars" not in got


@pytest.mark.parametrize("backend", sorted(BACKENDS))
def test_service_flushes_after_warmup_build_nothing_in_enqueue(backend,
                                                               tmp_path):
    eng = _engine(backend)
    with AlignmentService(eng, mode="semiglobal", collect_tb=True,
                          max_wait_ms=1.0, min_fill=4) as service:
        def flush(seed):
            reads, refs = _pairs(4, seed=seed)
            futures = [service.submit(q, r) for q, r in zip(reads, refs)]
            return [f.result(timeout=300) for f in futures]

        flush(5)
        jax.profiler.start_trace(str(tmp_path))
        try:
            for seed in (6, 7, 8):
                flush(seed)
        finally:
            jax.profiler.stop_trace()
    enqueues = [stats for spans in _host_spans(tmp_path).values()
                for name, stats in spans if name == "serve.enqueue"]
    assert len(enqueues) >= 3
    assert [e["builds"] for e in enqueues] == [0] * len(enqueues)
