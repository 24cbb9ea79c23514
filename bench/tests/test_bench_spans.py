"""The program's host spans on a small synthetic trace."""

import pathlib
import sys
import types

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import loader  # noqa: E402
import spans  # noqa: E402
import trace_reduce  # noqa: E402

# Times in us. Window 0-100. Client line: map.batch 5-95 holding seed
# 5-20, chain 20-40, submit 40-50 and await 50-90 (a wait); a chain span
# 95-105 runs past the window. Dispatcher line: flush 45-60 holding
# enqueue 47-55; finalize 70-85 holding fetch 72-80 and decode 80-84.
# Device 0 runs two ops, 10-30 and 25-35, and a kernel 55-70; device 1
# is busy all window. Proto times: ps from the line's ns stamp.
TRACE = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 90000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 15000000
      stats { metadata_id: 20 int64_value: 2 } }
    events { metadata_id: 4 offset_ps: 20000000 duration_ps: 20000000 }
    events { metadata_id: 5 offset_ps: 40000000 duration_ps: 10000000 }
    events { metadata_id: 6 offset_ps: 50000000 duration_ps: 40000000
      stats { metadata_id: 21 int64_value: 1 } }
    events { metadata_id: 4 offset_ps: 95000000 duration_ps: 10000000 }
  }
  lines { id: 2 name: "python" timestamp_ns: 1000
    events { metadata_id: 7 offset_ps: 45000000 duration_ps: 15000000
      stats { metadata_id: 22 str_value: "fill" }
      stats { metadata_id: 23 int64_value: 4 }
      stats { metadata_id: 24 int64_value: 40 } }
    events { metadata_id: 8 offset_ps: 47000000 duration_ps: 8000000
      stats { metadata_id: 23 int64_value: 4 }
      stats { metadata_id: 25 int64_value: 2 } }
    events { metadata_id: 9 offset_ps: 70000000 duration_ps: 15000000
      stats { metadata_id: 25 int64_value: 1 } }
    events { metadata_id: 10 offset_ps: 72000000 duration_ps: 8000000 }
    events { metadata_id: 11 offset_ps: 80000000 duration_ps: 4000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "rapidx.map.batch" } }
  event_metadata { key: 3 value { id: 3 name: "rapidx.map.seed" } }
  event_metadata { key: 4 value { id: 4 name: "rapidx.map.chain" } }
  event_metadata { key: 5 value { id: 5 name: "rapidx.map.submit" } }
  event_metadata { key: 6 value { id: 6 name: "rapidx.map.await" } }
  event_metadata { key: 7 value { id: 7 name: "rapidx.serve.flush" } }
  event_metadata { key: 8 value { id: 8 name: "rapidx.serve.enqueue" } }
  event_metadata { key: 9 value { id: 9 name: "rapidx.serve.finalize" } }
  event_metadata { key: 10 value { id: 10 name: "rapidx.serve.fetch" } }
  event_metadata { key: 11 value { id: 11 name: "rapidx.serve.decode" } }
  stat_metadata { key: 20 value { id: 20 name: "reads" } }
  stat_metadata { key: 21 value { id: 21 name: "wait" } }
  stat_metadata { key: 22 value { id: 22 name: "cause" } }
  stat_metadata { key: 23 value { id: 23 name: "pairs" } }
  stat_metadata { key: 24 value { id: 24 name: "wait_us_sum" } }
  stat_metadata { key: 25 value { id: 25 name: "builds" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 20000000
      stats { metadata_id: 9 str_value: "jit(chain_anchors)/rapidx.chain/while" } }
    events { metadata_id: 2 offset_ps: 25000000 duration_ps: 10000000
      stats { metadata_id: 9 str_value: "jit(chain_anchors)/rapidx.chain/add" } }
    events { metadata_id: 3 offset_ps: 55000000 duration_ps: 15000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "while.1" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.2" } }
  event_metadata { key: 3 value { id: 3 name: "tpu_custom_call.1" } }
  stat_metadata { key: 9 value { id: 9 name: "tf_op" } }
}
planes {
  id: 3 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "kernel.1" } }
}
"""

US = 1e-6


@pytest.fixture(scope="module")
def traced():
    from jax.profiler import ProfileData
    profile = ProfileData.from_text_proto(TRACE)
    window = spans.window_ns(profile)
    return (trace_reduce.reduce(profile), spans.program_spans(profile, window),
            window)


def test_program_spans_are_clipped_to_the_window(traced):
    _, got, window = traced
    assert window == (1000, 101000)
    assert len(got) == 11
    assert {sp[0] for sp in got if sp[3].startswith("rapidx.map.")} == {0}
    assert {sp[0] for sp in got if sp[3].startswith("rapidx.serve.")} == {1}
    late = [sp for sp in got if sp[1] == 96000]
    assert late == [(0, 96000, 101000, "rapidx.map.chain", {})]
    flush = next(sp for sp in got if sp[3] == "rapidx.serve.flush")
    assert flush[4] == {"cause": "fill", "pairs": 4, "wait_us_sum": 40}


def test_self_time_leaves_out_the_child_spans(traced):
    _, got, _ = traced
    want = {"rapidx.map.batch": 5, "rapidx.map.seed": 15,
            "rapidx.map.chain": 20 + 5, "rapidx.map.submit": 10,
            "rapidx.map.await": 40, "rapidx.serve.flush": 7,
            "rapidx.serve.enqueue": 8, "rapidx.serve.finalize": 3,
            "rapidx.serve.fetch": 8, "rapidx.serve.decode": 4}
    assert spans.self_seconds(got) == pytest.approx(
        {k: v * US for k, v in want.items()})
    # The client line's stages add up to its whole window inside spans.
    client = [iv for iv in spans.self_intervals(got) if iv[0] == 0]
    assert sum(e - s for _, s, e, _, _ in client) == 95000


def test_stat_sums(traced):
    _, got, _ = traced
    assert spans.stat_sum(got, "rapidx.serve.enqueue", "builds") == 2
    assert spans.stat_sum(got, "rapidx.serve.finalize", "builds") == 1
    assert spans.stat_sum(got, "rapidx.serve.flush", "wait_us_sum") == 40
    assert spans.stat_sum(got, "rapidx.serve.flush", "pairs") == 4


def test_idle_is_attributed_to_the_working_stages(traced):
    reduced, got, window = traced
    # Device 0 idles 0-10, 35-55 and 70-100 us; device 1 never, so each
    # figure is half device 0's. Line 0 waits in map.await 50-90; the
    # gaps 0-5 and 85-90 have no working stage.
    want = {"none": 10, "map.seed": 5, "map.chain": 5 + 5,
            "map.submit": 5, "map.submit+serve.flush": 2,
            "map.submit+serve.enqueue": 3, "serve.enqueue": 5,
            "serve.finalize": 3, "serve.fetch": 8, "serve.decode": 4,
            "map.batch": 5}
    assert spans.idle_by_stage(reduced, got, window) == pytest.approx(
        {k: v * US / 2 for k, v in want.items()})
    assert spans.idle_explained_share(reduced, got, window) == \
        pytest.approx(50 / 60)


def test_a_gap_covered_only_by_a_wait_span_is_unexplained(traced):
    reduced, got, window = traced
    waits = [(0, 36000, 56000, "rapidx.map.await", {"wait": 1})]
    assert spans.idle_by_stage(reduced, waits, window) == pytest.approx(
        {"none": 60 * US / 2})
    assert spans.idle_explained_share(reduced, waits, window) == 0.0



# The readers' trace, built from (line, name, start us, end us, stats)
# with the window at 0-100 us. Dispatcher (line 2): a finalize
# -10..8 (builds 3) holding a fetch -8..5, both crossing the window's
# start; a flush 15-45 holding an enqueue 20-40 (builds 2) that holds a
# fetch 30-34; a finalize 50-70 (builds 1) holding a fetch 52-60 and a
# decode 60-65; an enqueue 90-110 (builds 4) crossing the end; a
# finalize 120-130 (builds 5) outside. Client (line 1): bench.map_batch
# 5-95; map.batch 5-95 holding map.submit 5-45 and map.await 45-95 (a
# wait). The device works 40-50 and 70-75.
HOST = [(1, "bench.window", 0, 100, {}),
        (1, "bench.map_batch", 5, 95, {}),
        (1, "rapidx.map.batch", 5, 95, {}),
        (1, "rapidx.map.submit", 5, 45, {}),
        (1, "rapidx.map.await", 45, 95, {"wait": 1}),
        (2, "rapidx.serve.finalize", -10, 8, {"builds": 3}),
        (2, "rapidx.serve.fetch", -8, 5, {}),
        (2, "rapidx.serve.flush", 15, 45, {}),
        (2, "rapidx.serve.enqueue", 20, 40, {"builds": 2}),
        (2, "rapidx.serve.fetch", 30, 34, {}),
        (2, "rapidx.serve.finalize", 50, 70, {"builds": 1}),
        (2, "rapidx.serve.fetch", 52, 60, {}),
        (2, "rapidx.serve.decode", 60, 65, {}),
        (2, "rapidx.serve.enqueue", 90, 110, {"builds": 4}),
        (2, "rapidx.serve.finalize", 120, 130, {"builds": 5})]
DEVICE = [(40, 50), (70, 75)]
#: The line's time stamp lies this far before the window (us), so that
#: every offset is positive.
T0 = 20


def text_proto(host, device) -> str:
    names = sorted({h[1] for h in host})
    stats = sorted({k for h in host for k in h[4]})
    lines = {}
    for line, name, s, e, st in host:
        ev = (f"events {{ metadata_id: {names.index(name) + 1} "
              f"offset_ps: {(s + T0) * 1000000} "
              f"duration_ps: {(e - s) * 1000000}")
        for k, v in st.items():
            ev += f" stats {{ metadata_id: {stats.index(k) + 1} " \
                  f"int64_value: {v} }}"
        lines.setdefault(line, []).append(ev + " }")
    out = 'planes { id: 1 name: "/host:CPU"\n'
    for line, evs in lines.items():
        out += (f'lines {{ id: {line} name: "python" timestamp_ns: 1000\n'
                + "\n".join(evs) + "}\n")
    out += "".join(f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                   f'name: "{n}" }} }}\n' for i, n in enumerate(names))
    out += "".join(f'stat_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                   f'name: "{n}" }} }}\n' for i, n in enumerate(stats))
    out += '}\nplanes { id: 2 name: "/device:TPU:0"\n'
    out += 'lines { id: 1 name: "XLA Ops" timestamp_ns: 1000\n'
    out += "".join(f"events {{ metadata_id: 1 offset_ps: {(s + T0) * 1000000}"
                   f" duration_ps: {(e - s) * 1000000} }}\n"
                   for s, e in device)
    return out + ('}\nevent_metadata { key: 1 value { id: 1 '
                  'name: "fusion.1" } }\n}\n')


@pytest.fixture(scope="module")
def served():
    from jax.profiler import ProfileData
    reduced = trace_reduce.reduce(
        ProfileData.from_text_proto(text_proto(HOST, DEVICE)))
    return types.SimpleNamespace(trace=reduced, record={"reads": 2})


@pytest.mark.parametrize("metric,want", [
    # (20-40 less its fetch 30-34) + 90-100 clipped, over 2 reads.
    ("enqueue_ms_per_read", (16 + 10) * US * 1e3 / 2),
    # 0-5 clipped + 30-34 + 52-60, over 2 reads.
    ("fetch_ms_per_read", (5 + 4 + 8) * US * 1e3 / 2),
    # The spans that overlap the window, the one crossing its start whole.
    ("builds_in_finalize.closed", 3 + 1),
])
def test_the_span_readers(served, metric, want):
    reader = loader.load_reader(metric)
    assert reader.read(served) == pytest.approx(want)
    untraced = types.SimpleNamespace(trace=None, record={"reads": 2})
    assert reader.read(untraced) is None
    silent = types.SimpleNamespace(trace={**served.trace, "spans": []},
                                   record={"reads": 2})
    assert reader.read(silent) is None


def test_idle_gaps_are_named_by_the_stages_working_in_them(served):
    # Idle 0-40: map.submit+serve.enqueue holds 16 us of it, more than
    # any other mix. Idle 75-100: no stage works in 75-90 (the client
    # waits), more than serve.enqueue's 90-100, so the harness's span.
    # Idle 50-70: the client waits; serve.fetch works 8 us of it.
    assert served.trace["gaps"] == [
        ("map.submit+serve.enqueue", pytest.approx(40 * US)),
        ("bench.map_batch", pytest.approx(25 * US)),
        ("serve.fetch", pytest.approx(20 * US))]
