"""Spans, program names and program-build counts (repro.obs) of the mapping and
serving path."""

import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.engine import AlignmentEngine
from repro.data.genome import ReadSimulator, random_genome
from repro.map import MinimizerIndex, ReadMapper
from repro.serve import AlignmentService

#: Host spans of one map_batch: the client thread's and the dispatcher's.
CLIENT = {"map.batch", "map.seed", "map.chain", "map.submit", "map.await"}
DISPATCHER = {"serve.flush", "serve.enqueue", "serve.finalize",
              "serve.fetch", "serve.decode"}


def test_spans_are_one_shared_no_op_without_a_trace(monkeypatch):
    def refuse(*args, **kwargs):
        raise AssertionError("TraceAnnotation created with no trace")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    assert not obs.enabled()
    assert obs.span("map.seed", reads=3) is obs.NO_SPAN
    assert obs.build_span("serve.enqueue", pairs=4) is obs.NO_SPAN
    with obs.span("map.chain") as sp:
        sp.set_metadata(sets=2)


def _host_spans(trace_dir) -> dict:
    """{line index: [(name, stats)]} of the `rapidx.` spans in the trace."""
    from jax.profiler import ProfileData
    [path] = pathlib.Path(trace_dir).rglob("*.xplane.pb")
    out: dict = {}
    line_id = 0
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(obs.PREFIX):
                    out.setdefault(line_id, []).append(
                        (ev.name[len(obs.PREFIX):], dict(ev.stats)))
            line_id += 1
    return out


@pytest.mark.parametrize("dispatch", ["pipelined", "persistent"])
def test_map_batch_spans_on_their_threads(tmp_path, dispatch):
    genome = random_genome(40_000, seed=3)
    index = MinimizerIndex(genome, k=13, w=8)
    sim = ReadSimulator(genome, "illumina", seed=4, rc_prob=0.5)
    reads = [sim.sample(150).read for _ in range(12)]
    engine = AlignmentEngine(backend="reference", capacity=8,
                             dispatch=dispatch)
    # The trace closes after the service has shut down, so the
    # dispatcher's last spans have ended inside it.
    jax.profiler.start_trace(str(tmp_path))
    try:
        with AlignmentService(engine, mode="semiglobal", collect_tb=True,
                              max_wait_ms=2.0) as service:
            mapper = ReadMapper(index, service,
                                priorities=("normal", "normal"))
            results = mapper.map_batch(reads)
    finally:
        jax.profiler.stop_trace()
    assert len(results) == len(reads)
    lines = _host_spans(tmp_path)
    names = {line: {n for n, _ in sps} for line, sps in lines.items()}
    [client] = [ln for ln, ns in names.items() if ns & CLIENT]
    [dispatcher] = [ln for ln, ns in names.items() if ns & DISPATCHER]
    assert client != dispatcher
    assert names[client] >= CLIENT - {"map.await"}
    assert names[client] <= CLIENT
    assert names[dispatcher] == DISPATCHER
    by_name: dict = {}
    for name, stats in lines[client] + lines[dispatcher]:
        by_name.setdefault(name, []).append(stats)
    [batch] = by_name["map.batch"]
    assert batch["reads"] == len(reads)
    [submit] = by_name["map.submit"]
    assert all(s.get("wait") == 1 for s in by_name.get("map.await", []))
    flushes = by_name["serve.flush"]
    assert {"cause", "pairs", "wait_us_sum"} <= set(flushes[0])
    assert sum(f["pairs"] for f in flushes) == submit["pairs"]
    assert all(f["wait_us_sum"] >= 0 for f in flushes)
    enqueues = by_name["serve.enqueue"]
    assert sum(e["pairs"] for e in enqueues) == submit["pairs"]
    for e in enqueues:
        assert {"builds", "slots", "band"} <= set(e)
        assert e["slots"] >= e["pairs"]
    assert all("builds" in f for f in by_name["serve.finalize"])
    assert all(f["bytes"] > 0 for f in by_name["serve.fetch"])


def test_build_span_counts_the_programs_its_thread_builds(tmp_path):
    obs.install()

    def seven_times(x):
        return x * 7

    x = jnp.arange(3)
    before = obs.programs_built().get("jit(seven_times)",
                                      {"count": 0})["count"]
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.build_span("serve.enqueue", pairs=1):
            jax.jit(seven_times)(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    built = obs.programs_built()["jit(seven_times)"]
    assert built["count"] == before + 1 and built["seconds"] > 0
    [[(name, stats)]] = _host_spans(tmp_path).values()
    assert name == "serve.enqueue"
    assert stats == {"pairs": 1, "builds": 1}


def test_device_programs_carry_their_scopes():
    """The chain program and the walker are named on the device trace by
    their programs, `jit(chain_anchors)` and `jit(decode_packed_tb)`."""
    from repro.core.traceback_device import decode_packed_tb
    from repro.map.chain import ChainParams, _chain_batch_fn

    p = ChainParams(k=13)
    a = np.zeros((16, p.anchors_cap), np.int32)
    chain = _chain_batch_fn(p.k, p.max_gap, p.max_diag_diff).lower(
        a, a, a.astype(bool))
    assert "module @jit_chain_anchors" in chain.as_text()

    n, t, band = 8, 40, 20
    walker = decode_packed_tb.lower(
        np.zeros((n, t, band // 2), np.uint8),
        np.zeros((n, t + 1), np.int32), np.ones(n, np.int32),
        np.ones(n, np.int32), band=band)
    assert "module @jit_decode_packed_tb" in walker.as_text()
